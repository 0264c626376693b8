"""Jit'd wrappers around the Pallas kernels, each beside its ``ref.py``
oracle, so the same model code runs everywhere.

``paged_attention`` and ``mamba_decode`` — the ops on the served path —
pick the kernel by the platform they are lowered for.  The others still
take ``use_pallas`` (kernel on TPU, or in interpret mode when forced;
oracle otherwise).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import ref
from .act_quant import act_dequant, act_quant
from .flash_attn import flash_attention
from .fused_ffn import fused_ffn
from .mamba_decode import mamba_decode_step
from .paged_decode_attn import paged_decode_attention
from .ssd_scan import ssd_scan


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def quantize_activations(x: jax.Array, use_pallas: bool = False,
                         interpret: bool = False):
    if use_pallas and (_on_tpu() or interpret):
        return tuple(act_quant(x, interpret=not _on_tpu()))
    return ref.act_quant_ref(x)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "out_dtype"))
def dequantize_activations(q: jax.Array, scales: jax.Array,
                           out_dtype=jnp.bfloat16, use_pallas: bool = False,
                           interpret: bool = False) -> jax.Array:
    if use_pallas and (_on_tpu() or interpret):
        return act_dequant(q, scales, out_dtype=out_dtype,
                           interpret=not _on_tpu())
    return ref.act_dequant_ref(q, scales, out_dtype)


@functools.partial(jax.jit, static_argnames=("activation", "use_pallas",
                                             "interpret"))
def gated_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array, activation: str = "silu",
              use_pallas: bool = False, interpret: bool = False) -> jax.Array:
    if use_pallas and (_on_tpu() or interpret):
        return fused_ffn(x, w_gate, w_up, w_down, activation=activation,
                         interpret=not _on_tpu())
    return ref.fused_ffn_ref(x, w_gate, w_up, w_down, activation)


@functools.partial(jax.jit, static_argnames=("causal", "window", "kv_len",
                                             "use_pallas", "interpret"))
def attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
              window: int = 0, kv_len: int | None = None,
              use_pallas: bool = False,
              interpret: bool = False) -> jax.Array:
    """q,k,v: (B, H, S, hd) with kv already broadcast to H."""
    b, h, s, hd = q.shape
    if use_pallas and (_on_tpu() or interpret):
        out = flash_attention(q.reshape(b * h, s, hd),
                              k.reshape(b * h, s, hd),
                              v.reshape(b * h, s, hd),
                              causal=causal, window=window, kv_len=kv_len,
                              interpret=not _on_tpu())
        return out.reshape(b, h, s, hd)
    return ref.flash_attn_ref(q, k, v, causal=causal, window=window,
                              kv_len=kv_len)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "interpret"))
def paged_attention(q: jax.Array, k_blocks: jax.Array, v_blocks: jax.Array,
                    tables: jax.Array, pos: jax.Array, k_new: jax.Array,
                    v_new: jax.Array, layer: jax.Array,
                    k_scale: jax.Array | None = None,
                    v_scale: jax.Array | None = None, window: int = 0,
                    scale: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """Single-query decode attention of layer ``layer`` straight off a
    BlockPool table.

    q: (slots, H, hd); k/v_blocks: (num_blocks, n_attn, bs, kvh, hd), the
    whole block-major pool; tables: (slots, mb) int32 runtime data; pos:
    (slots,) resident tokens; k/v_new: (slots, kvh, hd) current-token KV
    (not yet scattered); layer: int32 scalar, runtime data (the kernel's
    third scalar-prefetch operand); k/v_scale: optional
    (num_blocks, n_attn, bs) per-row int8 scales; ``scale``: the softmax
    scale (default 1/sqrt(hd)).  Neither the kernel nor the oracle slices
    a layer's plane out of the pool: each reads ``pool[blk, layer]``.

    Chosen by the platform the program is lowered for, with no flag that
    could pick the oracle on a chip: a TPU program gets the Pallas
    kernel, any other gets the ``ref.py`` oracle.  ``interpret=True``
    (tests only) runs the kernel in the Pallas interpreter anywhere."""
    args = (q, k_blocks, v_blocks, tables, pos, k_new, v_new, layer)
    kw = dict(k_scale=k_scale, v_scale=v_scale, window=window, scale=scale)
    if interpret:
        return paged_decode_attention(*args, interpret=True, **kw)
    return jax.lax.platform_dependent(
        *args,
        tpu=lambda *a: paged_decode_attention(*a, **kw),
        default=lambda *a: ref.paged_decode_attn_ref(*a, **kw))


def mamba_decode(state: jax.Array, layer: jax.Array, x: jax.Array,
                 dt: jax.Array, a: jax.Array, d: jax.Array, b: jax.Array,
                 c: jax.Array, interpret: bool = False):
    """The Mamba-2 decode update of layer ``layer`` of a slot-stacked state
    (layout and arguments: ``mamba_decode.mamba_decode_step``); returns
    ``(new state, y)``.

    Chosen by the platform the program is lowered for, as
    :func:`paged_attention` is: a TPU program gets the Pallas kernel,
    which updates the state in place, any other the ``ref.py`` oracle.
    Not jitted on its own: called inside the decode program, so the
    state it updates is that program's (donated) buffer."""
    args = (state, layer, x, dt, a, d, b, c)
    if interpret:
        return mamba_decode_step(*args, interpret=True)
    return jax.lax.platform_dependent(
        *args, tpu=mamba_decode_step, default=ref.mamba_decode_step_ref)


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret"))
def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, chunk: int = 128, use_pallas: bool = False,
        interpret: bool = False):
    """Layout: (BH, S, P) / (BH, S) / (BH,) / (BH, S, N)."""
    if use_pallas and (_on_tpu() or interpret):
        return tuple(ssd_scan(x, dt, a, b, c, chunk=chunk,
                              interpret=not _on_tpu()))
    return ref.ssd_scan_kernel_ref(x, dt, a, b, c, chunk)
