"""Unified model API: init / forward / prefill / decode_step.

Every architecture family exposes the same four entry points; the launcher,
serving runtime and middleware only talk to these.  Decode carries an
explicit cache pytree (attention KV, SSM state, conv state, cross-attn KV)
that is threaded through ``lax.scan`` over the stacked layers.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import hybrid_moe
from . import moe as moe_mod
from . import ssm as ssm_mod
from ..kernels import ops as kernel_ops
from ..kernels.act_quant import kv_dequant_rows, kv_quant_rows
from .configs import ATTN, HYBRID_MOE, MAMBA, ModelConfig
from .layers import (Params, dtype_of, embed_lookup, ffn_apply, matmul_w,
                     rms_norm, unembed)
from .runtime import DEFAULT_OPTIONS, RuntimeOptions
from .transformer import (_pattern_period, apply_stack, forward, init_params,
                          layer_window, lm_loss, walk_layers)

Cache = Dict[str, Any]

# the leaves of a dense cache that carry one entry per layer of the stack
_LAYER_LEAVES = ("k", "v", "ssm", "conv", "cross_k", "cross_v")

__all__ = ["init_params", "forward", "lm_loss", "init_cache", "prefill",
           "decode_step", "Cache", "init_slot_cache", "write_cache_slot",
           "sample_rows", "sample_logits",
           "sample_step", "SAMPLER_BRANCHES", "sampler_branch_index",
           "sample_batched_step", "admit_slot", "cool_slots",
           "batched_prefill_admit",
           "init_paged_pool", "init_paged_slot_cache",
           "paged_sample_batched_step", "paged_kernel_sample_batched_step",
           "paged_prefill_admit", "paged_thaw_write", "paged_copy_block"]


def _n_attn_layers(cfg: ModelConfig) -> int:
    if cfg.arch_type == HYBRID_MOE:
        return hybrid_moe.n_layers_of(cfg, ATTN)
    if cfg.arch_type in ("ssm", "hybrid"):
        return 0
    return cfg.num_layers


def _n_shared_sites(cfg: ModelConfig) -> int:
    if cfg.arch_type != "hybrid":
        return 0
    return cfg.num_layers // (cfg.shared_attn_period or cfg.num_layers)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               opts: RuntimeOptions = DEFAULT_OPTIONS) -> Cache:
    if cfg.arch_type == HYBRID_MOE:
        raise ValueError("a hybrid_moe stack is served on the paged path "
                         "only (decode_mode='paged', paged_kernel=True)")
    kv_dt = dtype_of(opts.kv_cache_dtype)
    hd = cfg.resolved_head_dim
    cache: Cache = {"pos": jnp.zeros((), jnp.int32)}
    n_attn = _n_attn_layers(cfg)
    if n_attn:
        shape = (n_attn, batch, max_seq, cfg.num_kv_heads, hd)
        cache["k"] = jnp.zeros(shape, kv_dt)
        cache["v"] = jnp.zeros(shape, kv_dt)
    if cfg.arch_type in ("ssm", "hybrid"):
        st, cv = ssm_mod.mamba_state_shapes(cfg, batch)
        cache["ssm"] = jnp.zeros((cfg.num_layers,) + st, jnp.float32)
        cache["conv"] = jnp.zeros((cfg.num_layers,) + cv, kv_dt)
    ns = _n_shared_sites(cfg)
    if ns:
        shape = (ns, batch, max_seq, cfg.num_kv_heads, hd)
        cache["shared_k"] = jnp.zeros(shape, kv_dt)
        cache["shared_v"] = jnp.zeros(shape, kv_dt)
    if cfg.is_encoder_decoder:
        shape = (cfg.num_layers, batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd)
        cache["cross_k"] = jnp.zeros(shape, kv_dt)
        cache["cross_v"] = jnp.zeros(shape, kv_dt)
    return cache


# ====================================================== slot-stacked cache ==
# The serving engine holds ONE cache pytree for all of its decode slots:
# every leaf of a batch=1 cache gains a leading ``(slots,)`` axis, including
# ``pos`` (each slot sits at its own sequence position).  ``vmap`` over that
# axis turns the per-sequence decode step into a single batched program, so
# per-tick decode cost scales with the model, not with the slot count.

def init_slot_cache(cfg: ModelConfig, slots: int, max_seq: int,
                    opts: RuntimeOptions = DEFAULT_OPTIONS) -> Cache:
    """A zeroed slot-stacked cache: ``init_cache(cfg, 1, ...)`` leaves with
    a leading ``(slots,)`` axis, plus a ``"sample"`` subtree holding each
    slot's sampling state (PRNG key, temperature, top-k) — per-slot policy
    rides in the cache pytree so it is donated, vmapped and slot-scattered
    exactly like the model state.  The zero init is greedy (temperature
    0), so a cache never touched by admission argmaxes."""
    one = init_cache(cfg, 1, max_seq, opts)
    stacked = jax.tree_util.tree_map(
        lambda a: jnp.zeros((slots,) + a.shape, a.dtype), one)
    stacked["sample"] = _init_sample_state(slots)
    return stacked


def _init_sample_state(slots: int) -> Cache:
    """The greedy zero of a slot cache's ``"sample"`` subtree."""
    return {"key": jnp.zeros((slots, 2), jnp.uint32),
            "temp": jnp.zeros((slots,), jnp.float32),
            "top_k": jnp.zeros((slots,), jnp.int32)}


def write_cache_slot(stacked: Cache, cache: Cache, slot: jax.Array) -> Cache:
    """Write a batch=1 cache (e.g. a fresh prefill) into slot ``slot`` of a
    slot-stacked cache.  ``slot`` may be traced, so one compiled program
    serves every slot index.  The two trees must match leaf-for-leaf —
    for an engine cache carrying a ``"sample"`` subtree use
    :func:`admit_slot`, which also sets the slot's sampling state."""
    return jax.tree_util.tree_map(
        lambda s, c: jax.lax.dynamic_update_index_in_dim(
            s, c.astype(s.dtype), slot, 0), stacked, cache)


def admit_slot(stacked: Cache, cache: Cache, slot: jax.Array,
               key: jax.Array, temp: jax.Array, top_k: jax.Array) -> Cache:
    """Write a prefilled batch=1 *model* cache plus its slot sampling state
    (``key (2,) uint32``, ``temp ()``, ``top_k ()``) into slot ``slot`` of
    a slot-stacked serving cache.  ``slot`` is traced — one program covers
    every slot index."""
    model_side = {k: v for k, v in stacked.items() if k != "sample"}
    out = write_cache_slot(model_side, cache, slot)
    s = stacked["sample"]

    def upd(arr, val):
        return jax.lax.dynamic_update_index_in_dim(
            arr, val.astype(arr.dtype), slot, 0)

    out["sample"] = {"key": upd(s["key"], key), "temp": upd(s["temp"], temp),
                     "top_k": upd(s["top_k"], top_k)}
    return out


def cool_slots(stacked: Cache, mask: jax.Array) -> Cache:
    """Zero the temperature of the slots ``mask`` ``(slots,) bool``
    names, leaving everything else of the slot-stacked cache as it is:
    a freed slot's stale sampling state then argmaxes, so it cannot hold
    the batch on a costlier branch of :func:`sample_rows`."""
    s = stacked["sample"]
    return dict(stacked, sample=dict(
        s, temp=jnp.where(mask, jnp.zeros_like(s["temp"]), s["temp"])))


# ================================================================ sampling ==
# The branches of :func:`sample_rows`, cheapest first; a batch takes the
# cheapest one that covers every row (``sampler_branch`` on the host
# predicts the same index from the rows' policies).
SAMPLER_BRANCHES = ("argmax", "temperature", "top_k")


def _sort_keys(x: jax.Array) -> jax.Array:
    """int32 keys ordered as ``lax.sort`` orders float32 ``x``: -0 equals
    0, every NaN equals NaN and sorts last."""
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    x = jnp.where(jnp.isnan(x), jnp.full_like(x, jnp.nan), x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _top_k_drop(scaled: jax.Array, top_k: jax.Array) -> jax.Array:
    """Which entries of each ``(n, V)`` row fall outside its ``top_k``
    largest, ties broken toward the lowest index (the stable descending
    rank), with ``top_k <= 0`` keeping the whole row.  One sort of the
    values finds each row's k-th largest; no index permutation is built.
    Of the entries equal to it, the first ``k - #larger`` stay."""
    vocab = scaled.shape[-1]
    keys = _sort_keys(-scaled)                 # ascending = descending rank
    kk = jnp.clip(top_k, 1, vocab)[:, None]
    kth = jnp.take_along_axis(jnp.sort(keys, axis=-1), kk - 1, axis=-1)
    above = keys < kth
    tied = keys == kth
    earlier_ties = jnp.cumsum(tied, axis=-1, dtype=jnp.int32) - tied
    keep = above | (tied & (earlier_ties
                            < kk - above.sum(-1, keepdims=True,
                                             dtype=jnp.int32)))
    return (top_k > 0)[:, None] & ~keep


def sample_rows(logits: jax.Array, keys: jax.Array, temps: jax.Array,
                top_ks: jax.Array, vocab: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Draw the next token of each row of ``(n, V)`` vocab-padded logits
    under the row's own key ``(n, 2)``, temperature and top-k.

    ``temp == 0`` is the exact argmax; ``top_k == 0`` samples the whole
    vocabulary and ``top_k == 1`` keeps only the argmax.  Which work the
    batch needs is decided on the device from the runtime policy: with no
    sampling row it argmaxes and nothing else; with no sampling row
    holding ``0 < top_k < vocab`` it takes each row's Gumbel-max without
    any sort; otherwise it masks each row below its k-th largest value
    first.  Every row's key is split on every call, sampled or not, so a
    stream depends only on its initial key and the emission index —
    never on which other rows share the batch.  Returns ``(tokens (n,),
    advanced keys (n, 2))``."""
    lg = logits[:, :vocab]
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    split = jax.vmap(jax.random.split)(keys)
    new_keys, subs = split[:, 0], split[:, 1]
    sampling = temps > 0

    def draw(top_k_mask: bool) -> jax.Array:
        scaled = lg.astype(jnp.float32) / jnp.maximum(
            temps.astype(jnp.float32), 1e-6)[:, None]
        if top_k_mask:
            scaled = jnp.where(_top_k_drop(scaled, top_ks),
                               jnp.finfo(jnp.float32).min, scaled)
        sampled = jax.vmap(jax.random.categorical)(subs, scaled)
        return jnp.where(sampling, sampled.astype(jnp.int32), greedy)

    tokens = jax.lax.switch(sampler_branch_index(temps, top_ks, vocab),
                            [lambda: greedy, lambda: draw(False),
                             lambda: draw(True)])
    return tokens, new_keys


def sampler_branch_index(temps: jax.Array, top_ks: jax.Array,
                         vocab: int) -> jax.Array:
    """The index into ``SAMPLER_BRANCHES`` of the cheapest branch of
    :func:`sample_rows` that covers every row: top-k if a sampling row
    has ``0 < top_k < vocab``, else temperature if any row samples, else
    argmax."""
    sampling = temps > 0
    return jnp.where(jnp.any(sampling & (top_ks > 0) & (top_ks < vocab)),
                     2, jnp.where(jnp.any(sampling), 1, 0))


def sample_logits(logits: jax.Array, key: jax.Array, temp: jax.Array,
                  top_k: jax.Array, vocab: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """:func:`sample_rows` for one sequence's logits row ``(V,)``: the
    single-row entry, bit-identical to that row in any batch.  Returns
    ``(token, advanced key)``."""
    tok, new_key = sample_rows(
        logits[None], key[None], jnp.asarray(temp, jnp.float32)[None],
        jnp.asarray(top_k, jnp.int32)[None], vocab)
    return tok[0], new_key[0]


def sample_step(params: Params, cfg: ModelConfig, cache: Cache,
                token: jax.Array, opts: RuntimeOptions = DEFAULT_OPTIONS
                ) -> Tuple[jax.Array, Cache]:
    """One sampling decode step for a single sequence.

    ``token`` is a ``()`` int32 scalar; ``cache`` is a batch=1 cache
    carrying a ``"sample"`` subtree ``{key (2,) uint32, temp (), top_k
    ()}`` (``decode_step`` threads unknown keys through untouched).
    :func:`sample_batched_step` runs the same ``decode_step`` under
    ``vmap`` and samples the rows together, so per-request streams are
    bit-identical across the batched and per-slot decode paths."""
    logits, c2 = decode_step(params, cfg, cache, token[None], opts)
    s = cache["sample"]
    nxt, new_key = sample_logits(logits[0], s["key"], s["temp"],
                                 s["top_k"], cfg.vocab_size)
    c2["sample"] = {"key": new_key, "temp": s["temp"], "top_k": s["top_k"]}
    return nxt, c2


def sample_batched_step(params: Params, cfg: ModelConfig, cache: Cache,
                        tokens: jax.Array,
                        opts: RuntimeOptions = DEFAULT_OPTIONS):
    """One sampling decode step over a slot-stacked cache.

    The per-slot temperature/top-k/PRNG key live in the cache's
    ``"sample"`` subtree, so heterogeneous per-slot policies run under ONE
    compiled program — sampling parameters are runtime data, not compile
    constants.  Slots with ``temp == 0`` produce exactly the greedy argmax
    (the engine's historical behavior).  Returns ``(next_tokens (slots,),
    positions (slots,), new cache)``."""
    def one(c: Cache, tok: jax.Array):
        logits, c2 = decode_step(params, cfg, c, tok[None], opts)
        return logits[0], c2

    logits, new_cache = jax.vmap(one)(cache, tokens)
    nxt, new_cache["sample"] = _sample_slots(logits, cache["sample"],
                                             cfg.vocab_size)
    return nxt, new_cache["pos"], new_cache


def _sample_slots(logits: jax.Array, sample: Cache, vocab: int
                  ) -> Tuple[jax.Array, Cache]:
    """:func:`sample_rows` over a slot cache's ``"sample"`` subtree:
    ``(tokens, the subtree with every key advanced)``."""
    nxt, keys = sample_rows(logits, sample["key"], sample["temp"],
                            sample["top_k"], vocab)
    return nxt, {"key": keys, "temp": sample["temp"],
                 "top_k": sample["top_k"]}


# ===================================================== batched admission ====
def batched_prefill_admit(params: Params, cfg: ModelConfig, stacked: Cache,
                          tokens: jax.Array, slot_ids: jax.Array,
                          keys: jax.Array, temps: jax.Array,
                          top_ks: jax.Array, opts: RuntimeOptions,
                          max_seq: int):
    """Prefill ``k`` left-padded same-bucket prompts in ONE call and
    scatter each row's cache, sampling state and first sampled token into
    its decode slot of the slot-stacked serving cache.

    ``tokens`` is ``(k, bucket)`` int32; ``slot_ids``/``keys``/``temps``/
    ``top_ks`` are per-row.  Rows are written in order, so callers pad a
    burst up to a k-bucket by *prepending* rows that target the first real
    row's slot — the real row then overwrites the padding's garbage.
    Returns ``((k,) first tokens, new stacked cache)``; each row's first
    token is drawn by the same :func:`sample_rows` the decode step uses
    (argmax when its temperature is 0)."""
    k, bucket = tokens.shape
    # the scratch cache is sized to the prompt *bucket*, not max_seq:
    # burst admission's transient memory is k×bucket + one max_seq row
    # (padded below, per row) instead of a second full k×max_seq cache —
    # the zero padding is identical to what a max_seq prefill writes
    cache = init_cache(cfg, k, min(bucket, max_seq), opts)
    logits, cache = prefill(params, cfg, tokens, cache, opts)
    first, new_keys = sample_rows(logits[:, -1], keys, temps, top_ks,
                                  cfg.vocab_size)
    out = stacked
    model_side = {key: v for key, v in stacked.items() if key != "sample"}
    for i in range(k):
        # batch lives at axis 1 of every array leaf; ``pos`` is a scalar
        # shared by the whole bucket (all rows are left-padded to it)
        row = jax.tree_util.tree_map(
            lambda a, i=i: a if a.ndim == 0 else
            jax.lax.slice_in_dim(a, i, i + 1, axis=1), cache)
        row = jax.tree_util.tree_map(
            lambda s, c: c if c.ndim == 0 else jnp.pad(
                c, [(0, t - n) for t, n in zip(s.shape[1:], c.shape)]),
            model_side, row)
        out = admit_slot(out, row, slot_ids[i], new_keys[i], temps[i],
                         top_ks[i])
    return first, out


# ============================================================ paged cache ==
# Block-paged KV: self-attention K/V live in a pool of fixed-size blocks
# shared by every slot, and each slot carries a host-side block table —
# a (slots, max_seq // block_size) int32 array of pool indices passed to
# the jitted step as *runtime data* (constant shape, so occupancy changes
# never recompile).  The paged step gathers each slot's blocks into a
# dense (1, max_seq) view and runs the *same* ``decode_step`` computation
# the dense engine runs: positions beyond ``pos`` read garbage from
# not-yet-written / trash blocks, but ``decode_attention`` replaces
# masked scores with NEG_INF, so their contribution is exactly 0 and the
# paged stream is bit-identical to the dense one.  Only self-attention
# K/V are paged — SSM/conv state is O(1) per sequence and stays a dense
# slot leaf, which is why paged mode requires an attention stack.

def init_paged_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
                    opts: RuntimeOptions = DEFAULT_OPTIONS) -> Cache:
    """The device block pool: ``{"k","v"}`` of shape ``(num_blocks,
    n_attn_layers, block_size, num_kv_heads, head_dim)``.  Block 0 is
    the trash block (see :mod:`repro.serving.paging`).

    ``opts.kv_dtype == "int8"`` stores the blocks int8 and adds
    ``{"k_scale","v_scale"}`` leaves of shape ``(num_blocks, n_attn,
    block_size)`` — one f32 scale per KV *row* (token × layer), the
    append granularity of both prefill blockify and the decode scatter.
    Every paged writer quantizes through :func:`kv_quant_rows` and every
    reader (gather step, kernel step, engine freeze) dequantizes, so the
    pool is ~4x denser for the same HBM."""
    n_attn = _n_attn_layers(cfg)
    if not n_attn:
        raise ValueError("paged decode requires an attention stack "
                         f"(arch_type={cfg.arch_type!r} has no KV cache)")
    if opts.kv_dtype not in ("auto", "int8"):
        raise ValueError(f"kv_dtype={opts.kv_dtype!r} (want 'auto' or 'int8')")
    store_int8 = opts.kv_dtype == "int8"
    kv_dt = jnp.int8 if store_int8 else dtype_of(opts.kv_cache_dtype)
    shape = (num_blocks, n_attn, block_size, cfg.num_kv_heads,
             cfg.resolved_head_dim)
    pool = {"k": jnp.zeros(shape, kv_dt), "v": jnp.zeros(shape, kv_dt)}
    if store_int8:
        sshape = (num_blocks, n_attn, block_size)
        pool["k_scale"] = jnp.zeros(sshape, jnp.float32)
        pool["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return pool


def init_paged_slot_cache(cfg: ModelConfig, slots: int, max_seq: int,
                          opts: RuntimeOptions = DEFAULT_OPTIONS) -> Cache:
    """A slot-stacked serving cache *without* the dense ``k``/``v``
    leaves (those live in the block pool); everything else — ``pos``,
    the ``"sample"`` subtree, cross-attention KV, a hybrid MoE stack's
    recurrent ``ssm``/``conv`` state — stays per-slot."""
    if cfg.arch_type == HYBRID_MOE:
        return {"pos": jnp.zeros((slots,), jnp.int32),
                "sample": _init_sample_state(slots),
                **hybrid_moe.init_state(cfg, slots)}
    stacked = init_slot_cache(cfg, slots, max_seq, opts)
    return {k: v for k, v in stacked.items() if k not in ("k", "v")}


def paged_sample_batched_step(params: Params, cfg: ModelConfig,
                              slot_cache: Cache, pool: Cache,
                              tokens: jax.Array, tables: jax.Array,
                              opts: RuntimeOptions = DEFAULT_OPTIONS):
    """One sampling decode step over paged KV.

    ``tables`` is ``(slots, max_seq // block_size)`` int32.  Per slot:
    gather its blocks into a dense view, run the exact dense
    ``decode_step``, slice the newly written KV row back out; the rows
    are sampled together, as ``sample_batched_step`` samples them.  One
    batched scatter then writes every slot's row into its tail block —
    active slots always own their tail block (buckets are block-aligned
    and thawed blocks are private), so no two real writes collide;
    masked slots write the trash block, whose content is never read
    unmasked.  Returns ``(next_tokens, positions, new slot cache,
    new pool)``.

    An int8 pool (``opts.kv_dtype == "int8"``) dequantizes per row while
    gathering and re-quantizes the newly written row before the scatter —
    the dense computation in the middle is unchanged."""
    if cfg.arch_type == HYBRID_MOE:
        raise ValueError("a hybrid_moe stack decodes through the kernel "
                         "step only (paged_kernel=True)")
    pk, pv = pool["k"], pool["v"]
    psk, psv = pool.get("k_scale"), pool.get("v_scale")
    _, n_attn, bs, kvh, hd = pk.shape
    mb = tables.shape[1]
    kv_dt = dtype_of(opts.kv_cache_dtype)

    def one(c: Cache, tok: jax.Array, tbl: jax.Array):
        def dense_view(p, scl):
            g = p[tbl]                          # (mb, n_attn, bs, kvh, hd)
            if scl is not None:
                g = kv_dequant_rows(g, scl[tbl], kv_dt)
            return jnp.moveaxis(g, 0, 1).reshape(n_attn, 1, mb * bs, kvh, hd)

        dense = dict(c)
        dense["k"], dense["v"] = dense_view(pk, psk), dense_view(pv, psv)
        wpos = c["pos"]                         # this step writes row wpos
        logits, c2 = decode_step(params, cfg, dense, tok[None], opts)
        row_k = jax.lax.dynamic_slice_in_dim(c2["k"], wpos, 1, axis=2)
        row_v = jax.lax.dynamic_slice_in_dim(c2["v"], wpos, 1, axis=2)
        slot_side = {k: v for k, v in c2.items() if k not in ("k", "v")}
        blk = tbl[wpos // bs]
        return (logits[0], slot_side, row_k[:, 0, 0], row_v[:, 0, 0],
                blk, wpos % bs)

    logits, new_cache, rk, rv, blks, offs = jax.vmap(one)(
        slot_cache, tokens, tables)
    nxt, new_cache["sample"] = _sample_slots(logits, slot_cache["sample"],
                                             cfg.vocab_size)
    new_pool = _scatter_kv_rows(pool, rk, rv, blks, offs)
    return nxt, new_cache["pos"], new_cache, new_pool


def _scatter_kv_rows(pool: Cache, rk: jax.Array, rv: jax.Array,
                     blks: jax.Array, offs: jax.Array) -> Cache:
    """Write one KV row per slot into its tail block.  ``rk``/``rv``:
    ``(slots, n_attn, kvh, hd)``; ``blks``/``offs``: ``(slots,)``.
    Quantizes the rows first when the pool stores int8."""
    new_pool = dict(pool)
    if "k_scale" in pool:
        rk, sk = kv_quant_rows(rk)
        rv, sv = kv_quant_rows(rv)
        new_pool["k_scale"] = pool["k_scale"].at[blks, :, offs].set(sk)
        new_pool["v_scale"] = pool["v_scale"].at[blks, :, offs].set(sv)
    new_pool["k"] = pool["k"].at[blks, :, offs].set(rk.astype(pool["k"].dtype))
    new_pool["v"] = pool["v"].at[blks, :, offs].set(rv.astype(pool["v"].dtype))
    return new_pool


def paged_kernel_sample_batched_step(params: Params, cfg: ModelConfig,
                                     slot_cache: Cache, pool: Cache,
                                     tokens: jax.Array, tables: jax.Array,
                                     opts: RuntimeOptions = DEFAULT_OPTIONS):
    """One sampling decode step over paged KV — no gather-to-dense detour.

    Drop-in twin of :func:`paged_sample_batched_step` (same signature,
    same return contract) selected by ``opts.paged_kernel``:
    :func:`paged_kernel_decode_logits`, then :func:`sample_rows`."""
    logits, new_pool, new_state = paged_kernel_decode_logits(
        params, cfg, slot_cache, pool, tokens, tables, opts)
    new_cache = dict(slot_cache, **new_state)
    nxt, new_cache["sample"] = _sample_slots(logits, slot_cache["sample"],
                                             cfg.vocab_size)
    new_cache["pos"] = slot_cache["pos"] + 1
    return nxt, new_cache["pos"], new_cache, new_pool


def paged_kernel_decode_logits(params: Params, cfg: ModelConfig,
                               slot_cache: Cache, pool: Cache,
                               tokens: jax.Array, tables: jax.Array,
                               opts: RuntimeOptions = DEFAULT_OPTIONS):
    """The forward half of :func:`paged_kernel_sample_batched_step`:
    ``(slots, vocab)`` logits for each slot's next token, the pool with
    every slot's new KV row scattered into its tail block, and the slot
    leaves the step updated besides (a hybrid MoE stack's recurrent
    state; ``{}`` for an attention stack).

    Instead of materializing a dense ``(mb * bs)`` view per slot, every
    layer's attention reads its pool blocks *through the block table* via
    :func:`kernel_ops.paged_attention` (the Pallas decode kernel on TPU,
    its ``ref.py`` oracle elsewhere).  The whole step is slot-batched
    directly — q/k/v projections and FFN run at batch = slots with
    per-slot rotary phases — rather than ``vmap`` of a batch-1 step.
    Tables and positions stay runtime data, so occupancy/fragmentation
    never recompiles; int8 pools pass their per-row scales straight into
    the kernel's block loop (dequant on chip, never in HBM).  The walk
    carries each attention layer's index beside its weights, and the
    kernel reads ``pool[blk, layer]`` out of the whole block-major pool:
    nothing slices a layer's plane out of the pool or transposes it."""
    from .layers import (cast_params, mask_padded_logits_raw,
                         rotary_embedding)
    pos = slot_cache["pos"]                                   # (slots,)
    bs = pool["k"].shape[2]
    tables = tables.astype(jnp.int32)
    blks = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    if cfg.arch_type == HYBRID_MOE:
        logits, rk, rv, state = hybrid_moe.paged_decode(
            params, cfg, slot_cache, pool, tokens, tables, opts)
        return logits, _scatter_kv_rows(pool, rk, rv, blks, pos % bs), state
    act_dt = dtype_of(cfg.activation_dtype)
    params = cast_params(params, act_dt)
    x = embed_lookup(params["embed"], tokens).astype(act_dt)  # (slots, D)
    hd = pool["k"].shape[-1]
    attn_ids = jnp.arange(pool["k"].shape[1], dtype=jnp.int32)
    rope = rotary_embedding(pos[:, None], hd, cfg.rope_theta)
    cross = None
    if cfg.is_encoder_decoder:
        # cross KV is a slot leaf (slots, n_layers, 1, enc_seq, kvh, hd);
        # walk it layer-major, dropping the batch=1 axis
        cross = tuple(jnp.moveaxis(slot_cache[name][:, :, 0], 0, 1)
                      for name in ("cross_k", "cross_v"))

    def layer(x, kind, sl):
        weights, j, ckv = sl
        return _attn_decode(weights, x, rope,
                            _paged_kv(pool, j, tables, pos), cfg,
                            opts, window=layer_window(cfg, kind),
                            cross_kv=ckv)

    x, (row_k, row_v), _ = walk_layers(
        cfg, x, (params["layers"], attn_ids, cross), layer,
        num_layers=cfg.num_layers)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    logits = mask_padded_logits_raw(logits, cfg.vocab_size)

    # one batched scatter of every layer's new row into each slot's tail
    # block (same collision-freedom argument as the gather step)
    rk = jnp.moveaxis(row_k, 0, 1)                  # (slots, n_attn, kvh, hd)
    rv = jnp.moveaxis(row_v, 0, 1)
    return logits, _scatter_kv_rows(pool, rk, rv, blks, pos % bs), {}


def paged_prefill_admit(params: Params, cfg: ModelConfig, slot_cache: Cache,
                        pool: Cache, tokens: jax.Array, slot_ids: jax.Array,
                        keys: jax.Array, temps: jax.Array,
                        top_ks: jax.Array, dest_blocks: jax.Array,
                        opts: RuntimeOptions):
    """Burst admission into the paged cache: prefill ``(k, bucket)``
    left-padded prompts in ONE call, scatter each row's KV into its
    destination pool blocks and its non-KV leaves + sampling state into
    its slot.  ``dest_blocks`` is ``(k, bucket // block_size)`` int32 —
    padding rows target the trash block.  Returns ``((k,) first tokens,
    (k, vocab) last-position logits, new slot cache, new pool)``; the
    logits rows let the caller cache the prefill for prefix reuse."""
    k, bucket = tokens.shape
    _, n_attn, bs, kvh, hd = pool["k"].shape
    nblk = bucket // bs
    if cfg.arch_type == HYBRID_MOE:
        logits, kv_k, kv_v, state = hybrid_moe.prefill(params, cfg, tokens,
                                                       opts)
        # each row's recurrent state goes to its slot whole
        rows = [{"pos": jnp.int32(bucket),
                 **{name: a[i] for name, a in state.items()}}
                for i in range(k)]
    else:
        cache = init_cache(cfg, k, bucket, opts)
        logits, cache = prefill(params, cfg, tokens, cache, opts)
        kv_k, kv_v = cache["k"], cache["v"]
        model_side = {key: v for key, v in slot_cache.items()
                      if key != "sample"}
        row_src = {key: v for key, v in cache.items() if key not in ("k", "v")}
        rows = []
        for i in range(k):
            row = jax.tree_util.tree_map(
                lambda a, i=i: a if a.ndim == 0 else
                jax.lax.slice_in_dim(a, i, i + 1, axis=1), row_src)
            rows.append(jax.tree_util.tree_map(
                lambda s, c: c if c.ndim == 0 else jnp.pad(
                    c, [(0, t - n) for t, n in zip(s.shape[1:], c.shape)]),
                model_side, row))
    last = logits[:, -1]
    first, new_keys = sample_rows(last, keys, temps, top_ks, cfg.vocab_size)

    def blockify(a):                     # (n_attn, k, bucket, kvh, hd)
        a = jnp.moveaxis(a, 0, 1).reshape(k, n_attn, nblk, bs, kvh, hd)
        return jnp.moveaxis(a, 2, 1).reshape(k * nblk, n_attn, bs, kvh, hd)

    flat = dest_blocks.reshape(-1)
    new_pool = dict(pool)
    bk, bv = blockify(kv_k), blockify(kv_v)
    if "k_scale" in pool:                # quantize at append time
        bk, sk = kv_quant_rows(bk)
        bv, sv = kv_quant_rows(bv)
        new_pool["k_scale"] = pool["k_scale"].at[flat].set(sk)
        new_pool["v_scale"] = pool["v_scale"].at[flat].set(sv)
    new_pool["k"] = pool["k"].at[flat].set(bk.astype(pool["k"].dtype))
    new_pool["v"] = pool["v"].at[flat].set(bv.astype(pool["v"].dtype))
    out = slot_cache
    for i, row in enumerate(rows):
        out = admit_slot(out, row, slot_ids[i], new_keys[i], temps[i],
                         top_ks[i])
    return first, last, out, new_pool


def paged_thaw_write(pool: Cache, rows_k: jax.Array, rows_v: jax.Array,
                     ids: jax.Array) -> Cache:
    """Scatter a thawed request's densified KV back into pool blocks.
    ``rows_k``/``rows_v``: ``(nblk, n_attn, block_size, kvh, hd)``;
    ``ids``: ``(nblk,)`` freshly allocated (private) block indices.
    Frozen blobs stay portable (``kv_cache_dtype``), so an int8 pool
    re-quantizes on thaw — for rows that were quantized at freeze this is
    effectively the identity (the max-code row recovers its scale)."""
    new_pool = dict(pool)
    if "k_scale" in pool:
        rows_k, sk = kv_quant_rows(rows_k)
        rows_v, sv = kv_quant_rows(rows_v)
        new_pool["k_scale"] = pool["k_scale"].at[ids].set(sk)
        new_pool["v_scale"] = pool["v_scale"].at[ids].set(sv)
    new_pool["k"] = pool["k"].at[ids].set(rows_k.astype(pool["k"].dtype))
    new_pool["v"] = pool["v"].at[ids].set(rows_v.astype(pool["v"].dtype))
    return new_pool


def paged_copy_block(pool: Cache, src: jax.Array, dst: jax.Array) -> Cache:
    """Copy-on-write: duplicate block ``src`` into ``dst`` (both traced,
    one program covers every pair).  Generic over the pool's leaves, so
    int8 scale planes ride along with their blocks."""
    return {name: arr.at[dst].set(arr[src]) for name, arr in pool.items()}


# =========================================================== decode blocks ==
def _apply_rot1(x: jax.Array, sin, cos):
    """x: (B, H, hd) one-token rotary."""
    from .layers import apply_rotary
    return apply_rotary(x[:, None], sin, cos)[:, 0]


def _attn_decode(layer: Params, x: jax.Array, rope, attend: Callable,
                 cfg: ModelConfig, opts: RuntimeOptions, *, window: int,
                 cross_kv=None):
    """One-token attention block.  x: (B, D); ``rope`` the ``(sin, cos)``
    of each row's position.

    ``attend(q, k, v, window) -> (out (B, H, hd), kv)`` reads this layer's
    KV with the new token's rotated ``k``/``v`` ``(B, kvh, hd)`` in it:
    :func:`_dense_kv` writes them into a dense cache and returns it,
    :func:`_paged_kv` reads the pool through the block table and returns
    the new row for one batched scatter.  Returns ``(x, kv)``."""
    b, d = x.shape
    hd = cfg.resolved_head_dim
    h = rms_norm(x, layer["ln1"], cfg.norm_eps)
    a = layer["attn"]
    q = matmul_w(h, a["wq"]).reshape(b, cfg.num_heads, hd)
    k = matmul_w(h, a["wk"]).reshape(b, cfg.num_kv_heads, hd)
    v = matmul_w(h, a["wv"]).reshape(b, cfg.num_kv_heads, hd)
    if "bq" in a:
        q = q + a["bq"].reshape(cfg.num_heads, hd)
        k = k + a["bk"].reshape(cfg.num_kv_heads, hd)
        v = v + a["bv"].reshape(cfg.num_kv_heads, hd)
    q = _apply_rot1(q, *rope)
    k = _apply_rot1(k, *rope)
    out, kv = attend(q, k, v, window or opts.decode_window)
    x = x + matmul_w(out.reshape(b, cfg.num_heads * hd), a["wo"]).astype(x.dtype)

    if cross_kv is not None and "cross" in layer:
        hq = rms_norm(x, layer["ln_cross"], cfg.norm_eps)
        c = layer["cross"]
        qc = (hq @ c["wq"]).reshape(b, cfg.num_heads, hd)
        ck, cv = cross_kv
        # non-causal attention over the fixed encoder output
        out = attn_mod.decode_attention(qc, ck.astype(x.dtype),
                                        cv.astype(x.dtype),
                                        jnp.int32(ck.shape[1] - 1), window=0)
        x = x + (out.reshape(b, cfg.num_heads * hd) @ c["wo"]).astype(x.dtype)

    h2 = rms_norm(x, layer["ln2"], cfg.norm_eps)
    if cfg.arch_type == "moe":
        y = moe_mod.moe_apply_decode(layer["moe"], h2, cfg)
    else:
        y = ffn_apply(layer["ffn"], h2, gated=cfg.gated_ffn,
                      activation=cfg.activation)
    return x + y.astype(x.dtype), kv


def _dense_kv(k_cache, v_cache, pos) -> Callable:
    """The ``attend`` of :func:`_attn_decode` over one layer's dense cache
    ``(B, max_seq, kvh, hd)`` at the shared position ``pos``: writes the
    new row, attends, returns the updated ``(k_cache, v_cache)``."""
    def attend(q, k, v, window):
        kc, vc = attn_mod.update_kv_cache(k_cache, v_cache, k, v, pos)
        return attn_mod.decode_attention(q, kc, vc, pos, window=window), (kc, vc)
    return attend


def _paged_kv(pool, layer, tables, pos) -> Callable:
    """The ``attend`` of :func:`_attn_decode` over attention layer
    ``layer`` (an int32 scalar, runtime data) of the whole block-major
    pool ``(num_blocks, n_attn, bs, kvh, hd)`` (with ``k_scale``/
    ``v_scale`` ``(num_blocks, n_attn, bs)`` in an int8 pool) through the
    block tables, each slot at its own ``pos``:
    :func:`kernel_ops.paged_attention` (Pallas on TPU, the layer index a
    scalar-prefetch operand; the ``ref.py`` oracle elsewhere).  Returns
    the new ``(k, v)`` row, which the step scatters into the pool once
    for every layer."""
    def attend(q, k, v, window):
        out = kernel_ops.paged_attention(
            q, pool["k"], pool["v"], tables, pos, k, v, layer,
            pool.get("k_scale"), pool.get("v_scale"), window=window)
        return out, (k, v)
    return attend


def _mamba_decode(layer: Params, x: jax.Array, ssm_state, conv_state,
                  cfg: ModelConfig):
    h = rms_norm(x, layer["ln"], cfg.norm_eps)
    y, ssm_state, conv_state = ssm_mod.mamba_step(
        layer["mamba"], h, ssm_state, conv_state.astype(h.dtype), cfg)
    return x + y.astype(x.dtype), ssm_state, conv_state


# ================================================================= decode ==
def decode_step(params: Params, cfg: ModelConfig, cache: Cache,
                token: jax.Array, opts: RuntimeOptions = DEFAULT_OPTIONS
                ) -> Tuple[jax.Array, Cache]:
    """Generate logits for ONE new token per sequence.

    token: (B,) int32.  Returns (logits (B, vocab), updated cache).
    """
    from .layers import cast_params, rotary_embedding
    act_dt = dtype_of(cfg.activation_dtype)
    params = cast_params(params, act_dt)
    x = embed_lookup(params["embed"], token).astype(act_dt)  # (B, D)
    pos = cache["pos"]
    _, shared_after = _pattern_period(cfg)
    rope = rotary_embedding(pos[None, None], cfg.resolved_head_dim,
                            cfg.rope_theta)                   # (1, 1, half)
    new_cache = dict(cache)
    # per-layer leaves, walked beside the weights
    state = {name: cache[name] for name in _LAYER_LEAVES if name in cache}

    def layer(x, kind, sl):
        weights, st = sl
        if kind == MAMBA:
            x, s1, c1 = _mamba_decode(weights, x, st["ssm"], st["conv"], cfg)
            return x, {"ssm": s1, "conv": c1.astype(cache["conv"].dtype)}
        ckv = (st["cross_k"], st["cross_v"]) if "cross_k" in st else None
        x, (k1, v1) = _attn_decode(weights, x, rope,
                                   _dense_kv(st["k"], st["v"], pos), cfg, opts,
                                   window=layer_window(cfg, kind),
                                   cross_kv=ckv)
        return x, {"k": k1, "v": v1}

    shared = period_xs = None
    if shared_after and "shared_attn" in params and "shared_k" in cache:
        def shared(x, skv):
            return _attn_decode(params["shared_attn"], x, rope,
                                _dense_kv(*skv, pos), cfg, opts, window=0)
        period_xs = (cache["shared_k"], cache["shared_v"])
    x, outs, shared_kv = walk_layers(
        cfg, x, (params["layers"], state), layer, num_layers=cfg.num_layers,
        epilogue=shared, period_xs=period_xs)
    new_cache.update(outs)
    if shared_kv is not None:
        new_cache["shared_k"], new_cache["shared_v"] = shared_kv

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    from .layers import mask_padded_logits_raw
    logits = mask_padded_logits_raw(logits, cfg.vocab_size)
    new_cache["pos"] = pos + 1
    return logits, new_cache


# ================================================================ prefill ==
def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            cache: Cache, opts: RuntimeOptions = DEFAULT_OPTIONS, *,
            encoder_frames: Optional[jax.Array] = None,
            vision_embeds: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Cache]:
    """Process a prompt, filling the cache.  Returns (logits, cache).

    A single scanned walk over the stacked layers computes activations AND
    captures per-layer cache entries (attention K/V, SSM final state, conv
    tail, cross-attn K/V) as scan outputs.
    """
    from .layers import cast_params
    act_dt = dtype_of(cfg.activation_dtype)
    params = cast_params(params, act_dt)
    x = embed_lookup(params["embed"], tokens).astype(act_dt)
    if cfg.vision_embed_dim and vision_embeds is not None:
        v = (vision_embeds.astype(act_dt) @ params["vision_proj"]["w"]
             + params["vision_proj"]["b"]).astype(act_dt)
        # vision embeddings occupy the first n_vis positions; the token ids
        # at those positions are placeholders (paper: modality frontend stub)
        x = jnp.concatenate([v, x[:, v.shape[1]:]], axis=1)
    new_cache = dict(cache)
    b, s = x.shape[0], x.shape[1]
    if "k" in cache:
        max_seq = cache["k"].shape[2]
    elif "shared_k" in cache:
        max_seq = cache["shared_k"].shape[2]
    else:
        max_seq = s
    hd = cfg.resolved_head_dim

    cross_src = None
    if cfg.is_encoder_decoder and encoder_frames is not None:
        enc = encoder_frames.astype(act_dt)
        enc, _ = apply_stack(params["encoder"], enc, cfg,
                             opts.replace(attn_impl="full"), causal=False)
        cross_src = rms_norm(enc, params["encoder_norm"], cfg.norm_eps)

    _, shared_after = _pattern_period(cfg)
    kv_dt = dtype_of(opts.kv_cache_dtype)
    has_cross = cfg.is_encoder_decoder and cross_src is not None

    def pad_kv(kk):
        return jnp.pad(kk.astype(kv_dt),
                       ((0, 0), (0, max_seq - s), (0, 0), (0, 0)))

    def layer(x, kind, weights):
        if kind == MAMBA:
            h = rms_norm(x, weights["ln"], cfg.norm_eps)
            y, st, cv = ssm_mod.mamba_prefill_states(weights["mamba"], h, cfg)
            return x + y.astype(x.dtype), {"ssm": st, "conv": cv.astype(kv_dt)}
        x, kk, vv = _attn_prefill_kv(weights, x, cfg, opts,
                                     window=layer_window(cfg, kind),
                                     cross_src=cross_src)
        out = {"k": pad_kv(kk), "v": pad_kv(vv)}
        if has_cross:
            c = weights["cross"]
            se = cross_src.shape[1]
            out["cross_k"] = (cross_src @ c["wk"]).reshape(
                b, se, cfg.num_kv_heads, hd).astype(kv_dt)
            out["cross_v"] = (cross_src @ c["wv"]).reshape(
                b, se, cfg.num_kv_heads, hd).astype(kv_dt)
        return x, out

    def shared(x, _):
        x, kk, vv = _attn_prefill_kv(params["shared_attn"], x, cfg, opts,
                                     window=0)
        return x, (pad_kv(kk), pad_kv(vv))

    x, outs, shared_kv = walk_layers(
        cfg, x, params["layers"], layer, num_layers=cfg.num_layers,
        epilogue=shared if shared_after and "shared_attn" in params else None)
    new_cache.update(outs)
    if shared_kv is not None:
        new_cache["shared_k"], new_cache["shared_v"] = shared_kv

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    from .layers import mask_padded_logits_raw
    logits = mask_padded_logits_raw(logits, cfg.vocab_size)
    new_cache["pos"] = jnp.int32(s)
    return logits, new_cache


def _attn_prefill_kv(layer, x, cfg, opts, window: int = 0, cross_src=None):
    """Run a transformer block, returning (x, K, V) of the self-attention."""
    from .layers import apply_rotary, rotary_embedding
    from .transformer import transformer_block

    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    h = rms_norm(x, layer["ln1"], cfg.norm_eps)
    q, k, v = attn_mod.qkv_project(layer["attn"], h, cfg.num_heads,
                                   cfg.num_kv_heads, hd)
    sin, cos = rotary_embedding(jnp.arange(s)[None, :], hd, cfg.rope_theta)
    k_rot = apply_rotary(k, sin, cos)
    x, _ = transformer_block(layer, x, cfg, opts, window=window,
                             causal=True, cross_src=cross_src)
    return x, k_rot, v
