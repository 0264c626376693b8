"""Pallas TPU kernel: paged single-query decode attention (serving ❽).

The per-token hot path of ``decode_mode="paged"``: each slot attends its
one new query against KV stored in fixed-size ``BlockPool`` blocks,
reading blocks *directly through the block table* instead of gathering
the pool to a dense cache first.  The table and per-slot lengths ride in
as scalar-prefetch operands (``PrefetchScalarGridSpec``), so they are
runtime data: occupancy, fragmentation and CoW remaps never change the
program — ``CompileCache`` keys stay put and ``recompiles == 0`` holds
across any block-table shape the engine produces.

Tiling: grid ``(slots, max_blocks)`` with the KV-block axis innermost
(sequential).  The index map for the K/V operands dereferences the table
(``tbl[s, j]``), so each program pulls exactly one pool block into VMEM;
online-softmax running state ``(m, l, acc)`` lives in VMEM scratch across
the sweep.  Tail/empty blocks (table entries pointing at the trash block)
are masked by ``col < pos`` — combined with the masked-row guard
(``m == NEG_INF`` → zero contribution) they contribute exactly nothing.
The current token's KV (``k_new``/``v_new``) has *not* been scattered
into the pool yet; it is folded into the running softmax at finalization
as an always-valid extra key, which keeps the append-then-attend ordering
out of the kernel entirely.

int8 KV: when per-row scales are passed, blocks are stored int8 and
dequantized inside the block loop — a row's scale factors out of its dot
products, so K's scales multiply the ``(.., bs)`` scores and V's the
probabilities, both lane-aligned — the pool holds ~4x more resident
slots for one extra VMEM operand of ``bs`` floats per block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
KERNEL_NAME = "paged_decode_attn"


def _paged_decode_kernel(*args, has_scales: bool, kvh: int, group: int,
                         block_size: int, num_blocks: int, window: int,
                         scale: float):
    if has_scales:
        (tbl_ref, pos_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref,
         ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr) = args
    else:
        (tbl_ref, pos_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref,
         o_ref, m_scr, l_scr, acc_scr) = args
        ks_ref = vs_ref = None
    s_id = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qg = (q_ref[0].astype(jnp.float32) * scale).reshape(kvh, group, -1)
    k = k_ref[0].astype(jnp.float32)                 # (bs, kvh, hd)
    v = v_ref[0].astype(jnp.float32)

    # scores (kvh, group, bs); pool col c is valid iff c < pos (and inside
    # the sliding window when one is set — the new token is position pos)
    s = jnp.einsum("kgh,ckh->kgc", qg, k,
                   preferred_element_type=jnp.float32)
    if has_scales:
        # a row scale factors out of its dot product: scale the (1, bs)
        # lane vector into the scores instead of relayouting it onto K
        s = s * ks_ref[0][None]
    pos = pos_ref[s_id]
    cols = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, block_size), 2)
    valid = cols < pos
    if window:
        valid &= cols > pos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                              # (kvh, group)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # fully-masked block sweep so far: keep the contribution exactly zero
    # (exp(NEG_INF - NEG_INF) would be 1 for every masked key)
    p = jnp.where(m_new[..., None] == NEG_INF, 0.0,
                  jnp.exp(s - m_new[..., None]))
    corr = jnp.exp(m_prev - m_new)                   # 0 when m_prev==NEG_INF
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
    pv = p * vs_ref[0][None] if has_scales else p    # V's row scales likewise
    acc_scr[...] = (acc_scr[...] * corr[..., None]
                    + jnp.einsum("kgc,ckh->kgh", pv, v,
                                 preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(j == num_blocks - 1)
    def _finalize():
        # fold in the current token's KV — always valid, so l_fin >= 1
        # even for a brand-new slot (pos == 0) whose pool sweep was fully
        # masked
        kn = kn_ref[0].astype(jnp.float32)           # (kvh, hd)
        vn = vn_ref[0].astype(jnp.float32)
        sn = jnp.einsum("kgh,kh->kg", qg, kn,
                        preferred_element_type=jnp.float32)
        m_fin = jnp.maximum(m_scr[...], sn)
        pn = jnp.exp(sn - m_fin)
        corr_f = jnp.exp(m_scr[...] - m_fin)
        l_fin = l_scr[...] * corr_f + pn
        # vn is (kvh, hd): lift to (kvh, 1, hd) so the kv-head axis lines
        # up with pn's — bare broadcasting would silently cross axes
        # whenever group == kvh
        acc_fin = (acc_scr[...] * corr_f[..., None]
                   + pn[..., None] * vn[:, None, :])
        out = acc_fin / jnp.maximum(l_fin, 1e-30)[..., None]
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_blocks: jax.Array,
                           v_blocks: jax.Array, tables: jax.Array,
                           pos: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           window: int = 0,
                           interpret: bool = False) -> jax.Array:
    """Single-query GQA attention straight off the block table.

    q: (slots, H, hd); k/v_blocks: (num_blocks, bs, kvh, hd) — ONE layer's
    pool slice; tables: (slots, mb) int32; pos: (slots,) int32 tokens
    already resident; k_new/v_new: (slots, kvh, hd) — the current token's
    KV, not yet scattered.  Optional k/v_scale: (num_blocks, bs) f32
    per-row int8 scales (pass both or neither).  Returns (slots, H, hd).
    """
    slots, h, hd = q.shape
    nb, bs, kvh, _ = k_blocks.shape
    mb = tables.shape[1]
    assert h % kvh == 0, (h, kvh)
    assert (k_scale is None) == (v_scale is None)
    group = h // kvh
    has_scales = k_scale is not None
    kernel = functools.partial(
        _paged_decode_kernel, has_scales=has_scales, kvh=kvh, group=group,
        block_size=bs, num_blocks=mb, window=window,
        scale=float(1.0 / np.sqrt(hd)))

    def at_slot(s, j, tbl, ps):                      # per-slot operands
        return (s, 0, 0)

    def at_table(s, j, tbl, ps):                     # table-indexed blocks
        return (tbl[s, j], 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, h, hd), at_slot),                      # q
        pl.BlockSpec((1, bs, kvh, hd), at_table),               # k block
        pl.BlockSpec((1, bs, kvh, hd), at_table),               # v block
        pl.BlockSpec((1, kvh, hd), at_slot),                    # k_new
        pl.BlockSpec((1, kvh, hd), at_slot),                    # v_new
    ]
    operands = [q, k_blocks, v_blocks, k_new, v_new]
    if has_scales:
        # scale planes ride as (num_blocks, 1, bs) so a block's last two
        # dims equal the array's — Mosaic refuses a (1, bs) block over
        # (num_blocks, bs), whose second-minor dim is neither 8-aligned
        # nor whole
        in_specs += [
            pl.BlockSpec((1, 1, bs), lambda s, j, tbl, ps: (tbl[s, j], 0, 0)),
            pl.BlockSpec((1, 1, bs), lambda s, j, tbl, ps: (tbl[s, j], 0, 0)),
        ]
        operands += [k_scale.reshape(nb, 1, bs), v_scale.reshape(nb, 1, bs)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd), at_slot),
        scratch_shapes=[
            pltpu.VMEM((kvh, group), jnp.float32),              # m
            pltpu.VMEM((kvh, group), jnp.float32),              # l
            pltpu.VMEM((kvh, group, hd), jnp.float32),          # acc
        ],
    )
    # one stable name for the kernel in compiled text and device traces
    with jax.named_scope(KERNEL_NAME):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((slots, h, hd), q.dtype),
            interpret=interpret,
            name=KERNEL_NAME,
        )(tables.astype(jnp.int32), pos.astype(jnp.int32), *operands)
