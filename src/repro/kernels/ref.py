"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each ``*_ref`` function is the semantic ground truth the kernels are
allclose-validated against in interpret mode, and the execution path of
every program lowered for a platform other than TPU.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------- act_quant ------
def act_quant_ref(x: jax.Array, block: int = 128
                  ) -> Tuple[jax.Array, jax.Array]:
    """Blockwise symmetric int8 quantization along the last dim.
    x: (M, N) with N % block == 0 -> (q int8 (M,N), scales f32 (M, N/block))."""
    m, n = x.shape
    xb = x.reshape(m, n // block, block).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = amax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q.reshape(m, n), scale[..., 0]


def act_dequant_ref(q: jax.Array, scale: jax.Array,
                    dtype=jnp.bfloat16) -> jax.Array:
    m, n = q.shape
    block = n // scale.shape[1]
    xb = q.reshape(m, n // block, block).astype(jnp.float32) * scale[..., None]
    return xb.reshape(m, n).astype(dtype)


def act_quant4_ref(x: jax.Array, block: int = 128
                   ) -> Tuple[jax.Array, jax.Array]:
    """Blockwise symmetric int4 quantization, two codes packed per byte.

    The code range is the symmetric [-7, 7] (the -8 point is deliberately
    unused so negation round-trips inside the code space and the scale is
    amax/7 on both sides); codes are stored biased by +8 into [1, 15] and
    packed little-nibble-first: byte j holds column 2j in its low nibble
    and column 2j+1 in its high nibble.

    x: (M, N) with N % block == 0 and N even
    -> (packed uint8 (M, N//2), scales f32 (M, N/block))."""
    m, n = x.shape
    xb = x.reshape(m, n // block, block).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = amax / 7.0 + 1e-12
    q = jnp.clip(jnp.round(xb / scale), -7, 7) + 8.0
    q = q.reshape(m, n).astype(jnp.uint8)
    lo, hi = q[:, 0::2], q[:, 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8), scale[..., 0]


def act_dequant4_ref(packed: jax.Array, scale: jax.Array,
                     dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of ``act_quant4_ref``: unpack nibbles (low nibble = even
    column), un-bias to [-7, 7], and rescale per block.
    packed: (M, N//2) uint8; scale: (M, N/block) -> (M, N)."""
    m, half = packed.shape
    n = half * 2
    lo = (packed & 0xF).astype(jnp.int8) - 8
    hi = (packed >> 4).astype(jnp.int8) - 8
    q = jnp.stack([lo, hi], axis=-1).reshape(m, n)
    block = n // scale.shape[1]
    xb = q.reshape(m, n // block, block).astype(jnp.float32) * scale[..., None]
    return xb.reshape(m, n).astype(dtype)


# ----------------------------------------------------------- fused_ffn -----
def fused_ffn_ref(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                  w_down: jax.Array, activation: str = "silu") -> jax.Array:
    """GeGLU/SwiGLU FFN: (act(x@wg) * (x@wu)) @ wd, f32 accumulation."""
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[activation]
    xf = x.astype(jnp.float32)
    h = act(xf @ w_gate.astype(jnp.float32)) * (xf @ w_up.astype(jnp.float32))
    return (h @ w_down.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------- flash_attn -----
def flash_attn_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int = 0,
                   kv_len: int | None = None) -> jax.Array:
    """Single-head-batched attention oracle.
    q: (B, H, S, hd); k, v: (B, H, S, hd)  (kv heads pre-broadcast).
    ``kv_len`` masks keys at positions >= kv_len; a query row with zero
    valid keys outputs exactly zero (matching the kernel's masked-row
    guard) instead of softmax's uniform average over -1e30 scores."""
    b, h, s, hd = q.shape
    scale = 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= rows >= cols
    if window:
        mask &= cols > rows - window
    if kv_len is not None:
        mask &= cols < kv_len
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    p = p * jnp.any(mask, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ----------------------------------------------------- paged decode attn ----
def paged_decode_attn_ref(q: jax.Array, k_blocks: jax.Array,
                          v_blocks: jax.Array, tables: jax.Array,
                          pos: jax.Array, k_new: jax.Array,
                          v_new: jax.Array, layer: jax.Array | int, *,
                          k_scale: jax.Array | None = None,
                          v_scale: jax.Array | None = None,
                          window: int = 0,
                          scale: float | None = None) -> jax.Array:
    """Single-query GQA attention over layer ``layer`` of a paged KV pool
    (oracle).

    q: (slots, H, hd); k/v_blocks: (num_blocks, n_attn, bs, kvh, hd) — the
    whole block-major pool; tables: (slots, mb) int32 block ids; pos:
    (slots,) — the number of tokens already in the pool (pool columns <
    pos are valid); k_new/v_new: (slots, kvh, hd) — the current token's
    KV, folded in as an always-valid extra key (it has NOT been scattered
    into the pool yet); layer: int32 scalar, the layer read, as
    ``pool[blk, layer]`` (no layer's plane is sliced out first).
    Optional k/v_scale: (num_blocks, n_attn, bs) f32 per-row int8 scales.
    ``window`` keeps pool columns > pos - window (the new token is position
    ``pos``, so with window w the valid set is (pos-w, pos]).  ``scale``
    is the softmax scale (default 1/sqrt(hd)).
    Returns (slots, H, hd) in q.dtype."""
    slots, h, hd = q.shape
    _, _, bs, kvh, _ = k_blocks.shape
    mb = tables.shape[1]
    g = h // kvh
    scale = 1.0 / np.sqrt(hd) if scale is None else scale

    def one(qi, tbl, p, kn, vn):
        rows = (mb * bs, kvh, hd)
        kf = k_blocks[tbl, layer].astype(jnp.float32).reshape(rows)
        vf = v_blocks[tbl, layer].astype(jnp.float32).reshape(rows)
        if k_scale is not None:
            kf = kf * k_scale[tbl, layer].reshape(mb * bs, 1, 1)
            vf = vf * v_scale[tbl, layer].reshape(mb * bs, 1, 1)
        cols = jnp.arange(mb * bs)
        valid = cols < p
        if window:
            valid &= cols > p - window
        kf = jnp.concatenate([kf, kn.astype(jnp.float32)[None]], axis=0)
        vf = jnp.concatenate([vf, vn.astype(jnp.float32)[None]], axis=0)
        valid = jnp.concatenate([valid, jnp.ones((1,), bool)])
        qg = qi.astype(jnp.float32).reshape(kvh, g, hd) * scale
        s = jnp.einsum("kgh,skh->kgs", qg, kf)
        s = jnp.where(valid[None, None, :], s, -1e30)
        p_attn = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("kgs,skh->kgh", p_attn, vf)
        return out.reshape(h, hd)

    return jax.vmap(one)(q, tables, pos, k_new, v_new).astype(q.dtype)


# ------------------------------------------------------------- ssd_scan ----
def ssd_scan_kernel_ref(x: jax.Array, dt: jax.Array, a: jax.Array,
                        b: jax.Array, c: jax.Array, chunk: int
                        ) -> Tuple[jax.Array, jax.Array]:
    """Per-(batch·head) SSD oracle in the kernel's layout.

    x: (BH, S, P); dt: (BH, S); a: (BH,); b, c: (BH, S, N).
    Returns (y (BH,S,P), final_state (BH,P,N))."""
    from repro.models.ssm import ssd_scan_ref

    def one(xi, dti, ai, bi, ci):
        y, st = ssd_scan_ref(xi[None, :, None, :], dti[None, :, None],
                             ai[None], bi[None, :, None, :],
                             ci[None, :, None, :], chunk=chunk)
        return y[0, :, 0, :], st[0, 0]

    return jax.vmap(one)(x, dt, a, b, c)


# ------------------------------------------------------- mamba decode ------
def mamba_decode_step_ref(state: jax.Array, layer: jax.Array, x: jax.Array,
                          dt: jax.Array, a: jax.Array, d: jax.Array,
                          b: jax.Array, c: jax.Array):
    """The Mamba-2 decode update of one layer of a slot-stacked state
    (oracle of :mod:`repro.kernels.mamba_decode`, same layout):
    ``s <- exp(dt A) s + B (dt x)``, ``y = sum_N(s C) + D x``.

    state: (slots, L, R, N, T) f32; x, dt: (slots, R, T); a, d: (R, T);
    b, c: (slots, N, T).  Returns ``(state with layer updated, y (slots,
    R, T) f32)``."""
    f32 = jnp.float32
    s = jax.lax.dynamic_index_in_dim(state, layer, axis=1, keepdims=False)
    x = x.astype(f32)
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))[:, :, None, :]        # (S, R, 1, T)
    s = s * decay + b.astype(f32)[:, None] * (dt * x)[:, :, None, :]
    y = jnp.sum(s * c.astype(f32)[:, None], axis=2) + d.astype(f32) * x
    return jax.lax.dynamic_update_index_in_dim(state, s, layer, axis=1), y
