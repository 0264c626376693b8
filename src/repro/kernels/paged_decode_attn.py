"""Pallas TPU kernel: paged single-query decode attention (serving ❽).

The per-token hot path of ``decode_mode="paged"``: each slot attends its
one new query against KV stored in fixed-size ``BlockPool`` blocks,
reading blocks *directly through the block table* instead of gathering
the pool to a dense cache first.  The kernel is handed the WHOLE
block-major pool ``(num_blocks, n_attn, bs, kvh, hd)`` and the index of
the attention layer to read; ``pool[blk, layer]`` is one contiguous
``(bs, kvh, hd)`` slab, so nothing outside the kernel transposes the pool
or slices a layer's plane out of it.  The table, the per-slot lengths and
the layer ride in as scalar-prefetch operands (``PrefetchScalarGridSpec``),
so they are runtime data: one compiled kernel serves every layer, and
occupancy, fragmentation and CoW remaps never change the program —
``CompileCache`` keys stay put and ``recompiles == 0`` holds across any
block-table shape the engine produces.

Tiling: one grid step per slot; inside it the kernel sweeps only the
slot's live rows ``[lo, pos)`` (``lo`` is 0, or ``pos - window + 1``
with a sliding window), in steps of ``R`` consecutive table entries,
``R * bs`` rows.  ``R`` comes from the shapes alone: the largest divisor
of ``mb`` with ``R * bs <= 128`` (one MXU pass of keys), so a geometry
whose ``mb`` has no such divisor falls back as far as ``R = 1``.  The
pool stays in HBM and the kernel copies each step's ``R`` K and V blocks
of its layer (``k_hbm.at[blk, layer]``) into VMEM itself,
double-buffered: step ``i + 1``'s copies are in flight
while step ``i`` computes.  A step's table columns are clamped into the
slot's live column range ``[lo // bs, (pos-1) // bs]``, so a dead block
is never fetched (columns past the tail re-read the tail block), and a
step that overlaps no live row does not exist: the loop runs from the
first live step to the last.  Rows inside a fetched tile that fall
outside ``[lo, pos)`` are masked by their true column, so a re-read
block contributes exactly nothing.

Within a step the ``R`` blocks form one ``(R*bs*kvh, hd)`` key matrix
(row ``c*kvh + k``: pool row ``c``, kv head ``k``) and every query head
meets every row in one matmul; the entries that pair a query head with
another kv head's row are masked like dead rows.  That spends ``kvh``
times the needed MXU work to keep the pool's ``(bs, kvh, hd)`` block
layout and avoid relayouting it, which the memory-bound sweep affords.
Scores, the online-softmax state ``(m, l, acc)`` and PV are float32.
The current token's KV (``k_new``/``v_new``) has *not* been scattered
into the pool yet; it is folded into the softmax after the sweep as an
always-valid extra key, which keeps the append-then-attend ordering out
of the kernel entirely; that fold runs for every slot, so an empty slot
(``pos == 0``, no live step) still attends to its new token.  A head dim
that is not a multiple of 128 lanes cannot be copied block by block out
of the pool (the chip copies whole lane tiles): its layer's plane is
sliced out and zero-padded up to 128 lanes in HBM on each call, the
padding adds zeros to every dot product and is sliced off the output.

int8 KV: when per-row scales are passed, blocks are stored int8 and
dequantized inside the sweep — a row's scale factors out of its dot
products, so K's scales multiply the scores and V's the probabilities.
The scales ``(num_blocks, n_attn, bs)`` of each slot's (clamped) columns
in the layer read (``scale[blk, layer]``) are gathered before the kernel
into the scores' column order, one ``(steps, R*bs*kvh)`` plane per slot,
so the pool holds ~4x more resident slots for a small per-call gather.
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
ROWS_PER_STEP = 128                # keys per sweep step: one MXU pass
LANES = 128


def blocks_per_step(mb: int, bs: int) -> int:
    """``R``: the largest divisor of ``mb`` with ``R * bs <= 128``."""
    return max((r for r in range(1, mb + 1)
                if mb % r == 0 and r * bs <= ROWS_PER_STEP), default=1)


def _live_cols(pos, block_size: int, window: int):
    """First and last table column holding a row of ``[lo, pos)`` — both
    0 for an empty slot, which has no live step."""
    last = jnp.maximum(pos - 1, 0) // block_size
    if not window:
        return 0, last
    return jnp.maximum(pos - window + 1, 0) // block_size, last


def _paged_decode_kernel(tbl_ref, pos_ref, layer_ref, q_ref, kn_ref, vn_ref,
                         key_ref, kv_head_ref, q_head_ref, *refs,
                         has_scales: bool, block_size: int, per_step: int,
                         window: int, scale: float):
    if has_scales:
        (ks_ref, vs_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem) = refs
    else:
        (k_hbm, v_hbm, o_ref, k_buf, v_buf, sem) = refs
    s_id = pl.program_id(0)
    pos = pos_ref[s_id]
    layer = layer_ref[0]
    first, last = _live_cols(pos, block_size, window)
    j0 = first // per_step
    n_steps = jnp.where(pos > 0, last // per_step - j0 + 1, 0)
    _, _, bs, kvh, hd = k_buf.shape
    rows = per_step * bs
    h = q_ref.shape[1]

    def copies(j, buf):
        """Step j's R K and V block copies into buffer ``buf``."""
        out = []
        for r in range(per_step):
            blk = tbl_ref[s_id, jnp.clip(j * per_step + r, first, last)]
            out += [pltpu.make_async_copy(k_hbm.at[blk, layer],
                                          k_buf.at[buf, r], sem.at[buf]),
                    pltpu.make_async_copy(v_hbm.at[blk, layer],
                                          v_buf.at[buf, r], sem.at[buf])]
        return out

    q = q_ref[0].astype(jnp.float32) * scale         # (h, hd)

    @pl.when(n_steps > 0)
    def _first_fetch():
        for c in copies(j0, 0):
            c.start()

    def sweep(i, carry):
        m_prev, l_prev, acc_prev = carry
        buf = i % 2
        j = j0 + i

        @pl.when(i + 1 < n_steps)
        def _prefetch():
            for c in copies(j + 1, 1 - buf):
                c.start()

        for c in copies(j, buf):
            c.wait()
        k = k_buf[buf].astype(jnp.float32).reshape(rows * kvh, hd)
        v = v_buf[buf].astype(jnp.float32).reshape(rows * kvh, hd)
        # scores (h, rows*kvh); column c*kvh + k is valid iff kv head k
        # serves the query head and lo <= c < pos (lo > 0 only with a
        # sliding window; the new token is position pos) — masked by its
        # true column, so a block re-read by the clamp contributes nothing
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_scales:
            s = s * ks_ref[0, pl.ds(j, 1), :]
        col = j * rows + key_ref[...]
        valid = (kv_head_ref[...] == q_head_ref[...]) & (col < pos)
        if window:
            valid &= col > pos - window
        s = jnp.where(valid, s, NEG_INF)
        # a live step holds a valid key for every head, so m_new is finite
        # and every masked entry's exp underflows to exactly 0
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)               # 0 when m_prev==NEG_INF
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = p * vs_ref[0, pl.ds(j, 1), :] if has_scales else p
        acc_new = acc_prev * corr + jnp.dot(pv, v,
                                            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(
        0, n_steps, sweep,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, hd), jnp.float32)))

    # fold in the current token's KV — always valid, so l_fin >= 1 even
    # for a brand-new slot (pos == 0) that had no live step
    kn = kn_ref[0].astype(jnp.float32)               # (h, hd), per q head
    vn = vn_ref[0].astype(jnp.float32)
    sn = jnp.sum(q * kn, axis=1, keepdims=True)
    m_fin = jnp.maximum(m, sn)
    pn = jnp.exp(sn - m_fin)
    corr_f = jnp.exp(m - m_fin)
    l_fin = l * corr_f + pn
    acc_fin = acc * corr_f + pn * vn
    o_ref[0] = (acc_fin / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_blocks: jax.Array,
                           v_blocks: jax.Array, tables: jax.Array,
                           pos: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, layer: jax.Array | int, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           window: int = 0, scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """Single-query GQA attention of layer ``layer`` straight off the block
    table.

    q: (slots, H, hd); k/v_blocks: (num_blocks, n_attn, bs, kvh, hd) — the
    WHOLE block-major pool, left in HBM; tables: (slots, mb) int32;
    pos: (slots,) int32 tokens already resident; k_new/v_new:
    (slots, kvh, hd) — the current token's KV, not yet scattered;
    layer: int32 scalar, the attention layer to read — runtime data beside
    ``tables`` and ``pos``, so one compiled kernel serves every layer.
    Optional k/v_scale: (num_blocks, n_attn, bs) f32 per-row int8 scales
    (pass both or neither).  ``scale``: the softmax scale (default
    ``1/sqrt(hd)``).  Returns (slots, H, hd).
    """
    slots, h, hd = q.shape
    _, _, bs, kvh, _ = k_blocks.shape
    mb = tables.shape[1]
    assert h % kvh == 0, (h, kvh)
    assert (k_scale is None) == (v_scale is None)
    group = h // kvh
    has_scales = k_scale is not None
    per_step = blocks_per_step(mb, bs)
    rows = per_step * bs
    tables = tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    kernel = functools.partial(
        _paged_decode_kernel, has_scales=has_scales, block_size=bs,
        per_step=per_step, window=window,
        scale=float(1.0 / np.sqrt(hd) if scale is None else scale))

    hd_p = -(-hd // LANES) * LANES                   # whole lane tiles
    read_layer = layer.reshape(1)
    if hd_p != hd:
        # the chip copies whole lane tiles, so a head dim off them cannot
        # be read block by block out of the pool: this layer's plane is
        # sliced out and zero-padded instead (a copy of the plane on every
        # call, which a lane-aligned head dim never makes); the padding
        # adds zeros to every dot product and is sliced off the output
        def lanes(x):
            return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, hd_p - hd)])

        q, k_new, v_new = lanes(q), lanes(k_new), lanes(v_new)
        k_blocks, v_blocks = (
            lanes(jax.lax.dynamic_slice_in_dim(b, layer, 1, axis=1))
            for b in (k_blocks, v_blocks))
        read_layer = jnp.zeros((1,), jnp.int32)

    # the scores' column c*kvh + k: pool row c of the step, kv head k
    cols = np.arange(rows * kvh, dtype=np.int32)
    key = jnp.asarray(cols[None] // kvh)
    kv_head = jnp.asarray(cols[None] % kvh)
    q_head = jnp.asarray(np.arange(h, dtype=np.int32)[:, None] // group)

    def at_slot(s, tbl, ps, lyr):                    # per-slot operands
        return (s, 0, 0)

    def whole(s, tbl, ps, lyr):                      # shape constants
        return (0, 0)

    in_specs = [
        pl.BlockSpec((1, h, hd_p), at_slot),                    # q
        pl.BlockSpec((1, h, hd_p), at_slot),                    # k_new
        pl.BlockSpec((1, h, hd_p), at_slot),                    # v_new
        pl.BlockSpec((1, rows * kvh), whole),                   # key
        pl.BlockSpec((1, rows * kvh), whole),                   # kv_head
        pl.BlockSpec((h, 1), whole),                            # q_head
    ]
    operands = [q, jnp.repeat(k_new, group, axis=1),
                jnp.repeat(v_new, group, axis=1), key, kv_head, q_head]
    if has_scales:
        # each slot's clamped columns' row scales of this layer, in the
        # scores' column order: (slots, steps, rows*kvh)
        first, last = _live_cols(pos[:, None], bs, window)
        blk = jnp.take_along_axis(
            tables, jnp.clip(jnp.arange(mb)[None], first, last), axis=1)

        def by_col(sc):
            per_row = sc[blk, layer].reshape(slots, mb // per_step, rows)
            return jnp.repeat(per_row, kvh, axis=2)

        in_specs += [pl.BlockSpec((1, mb // per_step, rows * kvh), at_slot)
                     ] * 2
        operands += [by_col(k_scale), by_col(v_scale)]
    in_specs += [pl.BlockSpec(memory_space=pltpu.HBM)] * 2     # the pool
    operands += [k_blocks, v_blocks]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd_p), at_slot),
        scratch_shapes=[
            pltpu.VMEM((2, per_step, bs, kvh, hd_p), k_blocks.dtype),
            pltpu.VMEM((2, per_step, bs, kvh, hd_p), v_blocks.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # one stable name for the kernel in compiled text and device traces
    with jax.named_scope(KERNEL_NAME):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((slots, h, hd_p), q.dtype),
            interpret=interpret,
            name=KERNEL_NAME,
        )(tables, pos, read_layer, *operands)
    return out[..., :hd] if hd_p != hd else out
