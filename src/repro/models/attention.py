"""Attention: GQA/MQA, chunked-flash prefill, banded sliding-window, decode.

Three execution paths, selectable by the engine (paper §III-C: the backend
engine picks the operator implementation that fits the resource context):

* ``full_attention``        — reference O(S^2) einsum path (small seq / tests)
* ``chunked_attention``     — flash-style online-softmax over KV chunks
                              (bounded memory; the 32k-prefill default)
* ``banded_attention``      — sliding-window with *static* KV slices, cost
                              O(S * (W + cq)) — the sub-quadratic variant the
                              long_500k configs select
* ``decode_attention``      — one token vs. a KV cache (full or windowed)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .layers import Params, apply_rotary, matmul_w, rotary_embedding

NEG_INF = -1e30


def attn_init(key: jax.Array, d_model: int, num_heads: int, num_kv_heads: int,
              head_dim: int, dtype, qkv_bias: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d_model)
    so = 1.0 / np.sqrt(num_heads * head_dim)
    p = {
        "wq": (jax.random.normal(ks[0], (d_model, num_heads * head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, num_kv_heads * head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, num_kv_heads * head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (num_heads * head_dim, d_model)) * so).astype(dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
    return p


def qkv_project(params: Params, x: jax.Array, num_heads: int,
                num_kv_heads: int, head_dim: int):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,K,hd)."""
    b, s, _ = x.shape
    q = matmul_w(x, params["wq"])
    k = matmul_w(x, params["wk"])
    v = matmul_w(x, params["wv"])
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return (q.reshape(b, s, num_heads, head_dim),
            k.reshape(b, s, num_kv_heads, head_dim),
            v.reshape(b, s, num_kv_heads, head_dim))


def _group(q: jax.Array, num_kv_heads: int) -> jax.Array:
    """(B,S,H,hd) -> (B,S,K,G,hd) for GQA einsums."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, num_kv_heads, h // num_kv_heads, hd)


# ------------------------------------------------------------- full (oracle)
def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int = 0,
                   q_offset: int | jax.Array = 0,
                   scale: float | None = None) -> jax.Array:
    """Reference attention.  q: (B,Sq,H,hd); k,v: (B,Sk,K,hd).  ``scale``
    is the softmax scale (default ``1/sqrt(hd)``)."""
    b, sq, h, hd = q.shape
    kheads = k.shape[2]
    qg = _group(q, kheads)
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    rows = jnp.arange(sq) + q_offset
    cols = jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= rows[:, None] >= cols[None, :]
    if window:
        mask &= cols[None, :] > rows[:, None] - window
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


# ------------------------------------------------------- chunked flash-style
def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      q_chunk: int = 512, k_chunk: int = 1024,
                      scale: float | None = None) -> jax.Array:
    """Online-softmax attention with bounded memory.

    Scans over query chunks (outer) and KV chunks (inner), keeping running
    max / denominator, so the (Sq x Sk) score matrix is never materialized.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kheads = k.shape[2]
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, sk)
    assert sq % q_chunk == 0 and sk % k_chunk == 0, (sq, q_chunk, sk, k_chunk)
    nq, nk = sq // q_chunk, sk // k_chunk
    scale = 1.0 / np.sqrt(hd) if scale is None else scale

    qr = _group(q, kheads).reshape(b, nq, q_chunk, kheads, h // kheads, hd)
    qr = jnp.moveaxis(qr, 1, 0)                        # (nq, b, cq, K, G, hd)
    kr = jnp.moveaxis(k.reshape(b, nk, k_chunk, kheads, hd), 1, 0)
    vr = jnp.moveaxis(v.reshape(b, nk, k_chunk, kheads, hd), 1, 0)

    def q_step(_, qi_idx):
        qi, iq = qi_idx
        row = iq * q_chunk + jnp.arange(q_chunk)

        def k_step(carry, kj_idx):
            m, l, acc = carry
            kj, vj, jk = kj_idx
            col = jk * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qi.astype(jnp.float32),
                           kj.astype(jnp.float32)) * scale
            mask = jnp.ones((q_chunk, k_chunk), bool)
            if causal:
                mask &= row[:, None] >= col[None, :]
            if window:
                mask &= col[None, :] > row[:, None] - window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p, vj.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kheads, h // kheads, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kheads, h // kheads, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kheads, h // kheads, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0), (kr, vr, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = jnp.moveaxis(out, 3, 1)                  # (b, cq, K, G, hd)
        return None, out.reshape(b, q_chunk, h, hd)

    _, chunks = jax.lax.scan(q_step, None, (qr, jnp.arange(nq)))
    out = jnp.moveaxis(chunks, 0, 1).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


# ------------------------------------------------- banded (sub-quadratic) ---
def banded_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     window: int, q_chunk: int = 512) -> jax.Array:
    """Sliding-window causal attention with static KV slices.

    Each query chunk [r0, r0+cq) attends to a *static-width* KV slice of
    ``window + cq`` columns ending at its last row — total cost
    O(S * (window + cq)) instead of O(S^2).
    """
    b, sq, h, hd = q.shape
    kheads = k.shape[2]
    q_chunk = min(q_chunk, sq)
    assert sq % q_chunk == 0
    nq = sq // q_chunk
    span = window + q_chunk
    scale = 1.0 / np.sqrt(hd)

    # pad K/V at the front so every slice is in range
    kp = jnp.pad(k, ((0, 0), (span, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (span, 0), (0, 0), (0, 0)))
    qr = jnp.moveaxis(_group(q, kheads).reshape(
        b, nq, q_chunk, kheads, h // kheads, hd), 1, 0)

    def q_step(_, qi_idx):
        qi, iq = qi_idx
        r0 = iq * q_chunk
        row = r0 + jnp.arange(q_chunk)
        # padded col range [r0 + q_chunk - span, r0 + q_chunk) maps to
        # absolute cols [r0 + q_chunk - span - span_pad ...]; slice start in
        # padded coords = r0 + q_chunk (end) - span + span(pad) = r0 + q_chunk
        start = r0 + q_chunk - span + span
        kj = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        col = start - span + jnp.arange(span)          # absolute column ids
        s = jnp.einsum("bqkgh,bskh->bkgqs", qi.astype(jnp.float32),
                       kj.astype(jnp.float32)) * scale
        mask = (col[None, :] >= 0) & (row[:, None] >= col[None, :]) \
            & (col[None, :] > row[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskh->bqkgh", p, vj.astype(jnp.float32))
        return None, out.reshape(b, q_chunk, h, hd)

    _, chunks = jax.lax.scan(q_step, None, (qr, jnp.arange(nq)))
    return jnp.moveaxis(chunks, 0, 1).reshape(b, sq, h, hd).astype(q.dtype)


# -------------------------------------------------------------------- decode
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window: int = 0) -> jax.Array:
    """One-token attention against a cache.

    q: (B, H, hd); caches: (B, S, K, hd); pos: scalar int32 (current index,
    cache already contains the new token at ``pos``).

    ``window > 0`` slices a static-width window ending at ``pos`` — per-token
    cost independent of cache length (the long_500k sub-quadratic path).
    """
    b, h, hd = q.shape
    kheads = k_cache.shape[2]
    s_len = k_cache.shape[1]
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(b, kheads, h // kheads, hd)

    if window and window < s_len:
        start = jnp.clip(pos + 1 - window, 0, s_len - window)
        kj = jax.lax.dynamic_slice_in_dim(k_cache, start, window, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v_cache, start, window, axis=1)
        col = start + jnp.arange(window)
    else:
        kj, vj = k_cache, v_cache
        col = jnp.arange(s_len)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                   kj.astype(jnp.float32)) * scale
    mask = col <= pos
    s = jnp.where(mask[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, vj.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


def update_kv_cache(k_cache: jax.Array, v_cache: jax.Array, k_new: jax.Array,
                    v_new: jax.Array, pos: jax.Array):
    """Insert one token.  k_new/v_new: (B, K, hd); pos scalar."""
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new[:, None].astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new[:, None].astype(v_cache.dtype), pos, axis=1)
    return k_cache, v_cache


def attention_block(params: Params, x: jax.Array, *, num_heads: int,
                    num_kv_heads: int, head_dim: int, rope_theta: float,
                    causal: bool = True, window: int = 0,
                    impl: str = "chunked", q_chunk: int = 512,
                    k_chunk: int = 1024, positions: Optional[jax.Array] = None
                    ) -> jax.Array:
    """Self-attention over a full sequence (train / prefill path)."""
    b, s, _ = x.shape
    q, k, v = qkv_project(params, x, num_heads, num_kv_heads, head_dim)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    sin, cos = rotary_embedding(positions, head_dim, rope_theta)
    q = apply_rotary(q, sin, cos)
    k = apply_rotary(k, sin, cos)
    if impl == "banded" and window:
        out = banded_attention(q, k, v, window=window, q_chunk=min(q_chunk, s))
    elif impl == "chunked" and s > q_chunk:
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                q_chunk=q_chunk, k_chunk=min(k_chunk, s))
    else:
        out = full_attention(q, k, v, causal=causal, window=window)
    return matmul_w(out.reshape(b, s, num_heads * head_dim), params["wo"])
