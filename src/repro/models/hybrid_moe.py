"""Hybrid MoE stacks (Granite-4.0-H): each layer a mixer, Mamba-2 or
attention, then a MoE feed-forward, served on the paged path.

A layer is ``cfg.layer_types[i]`` (``MAMBA`` or ``ATTN``):

    h   = x + rs * mixer(rms(x, ln1))
    out = h + rs * (moe(rms(h, ln2)) + shared(rms(h, ln2)))

with ``rs = cfg.residual_scale``, embeddings times ``cfg.embed_scale``, a
final RMSNorm and logits over ``cfg.logits_scale``.  Attention is GQA at
softmax scale ``cfg.resolved_attn_scale``, without rotary (NoPE).  The
Mamba-2 mixer is :func:`repro.models.ssm.mamba_prefill_states` over a
prompt; at decode its state update is
:func:`repro.kernels.ops.mamba_decode`.  The MoE routes over
``cfg.num_experts`` and computes the ``cfg.experts_held`` share
(:func:`repro.models.moe.moe_share_apply`); the shared expert, of width
``cfg.shared_expert_d_ff``, runs in full.

Parameters (the layout the program takes, and the chip benchmark's
family makes; weights stacked per mixer kind, in layer order):

* ``embed`` (V, D) — tied with the LM head; ``final_norm`` (D,);
* ``mamba``: ``ln1``, ``ln2`` (L_m, D); ``mixer``: ``in_proj`` (L_m, D,
  2*Di + 2*N + H), ``conv_w`` (L_m, Di + 2N, W), ``conv_b``, ``a_log``,
  ``d_skip``, ``dt_bias`` (L_m, H), ``norm_scale`` (L_m, Di),
  ``out_proj`` (L_m, Di, D); ``moe``;
* ``attn``: ``ln1``, ``ln2``; ``attn``: ``wq`` (L_a, D, H*hd), ``wk``,
  ``wv`` (L_a, D, K*hd), ``wo`` (L_a, H*hd, D); ``moe``;
* ``moe``: ``router`` (L, D, E), ``w_gate``, ``w_up`` (L, E_held, D, F),
  ``w_down`` (L, E_held, F, D), ``shared``: ``w_gate``, ``w_up`` (L, D,
  F_s), ``w_down`` (L, F_s, D).

Norm weights apply as ``1 + scale`` (:func:`repro.models.layers.rms_norm`).

The serving state is two kinds of cache: attention K/V in the engine's
block pool, and per slot the Mamba layers' SSM state ``ssm`` (slots, L_m,
R, N, T) and conv state ``conv`` (slots, L_m, W-1, Di + 2N), both float32
(the row layout of :mod:`repro.kernels.mamba_decode`).  Consecutive
Mamba layers run as one ``lax.scan`` over their stacked weights, so a
period's program stays the size of one layer's.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from ..kernels import ops as kernel_ops
from .configs import ATTN, MAMBA, ModelConfig
from .layers import (Params, cast_params, causal_conv1d_step, dtype_of,
                     embed_lookup, ffn_apply, gated_rms_norm,
                     mask_padded_logits_raw, rms_norm, unembed)
from .runtime import RuntimeOptions

STATE_LEAVES = ("ssm", "conv")


def _layer_at(stack: Params, j) -> Params:
    """Layer ``j`` (traced) of stacked weights, sliced inside the loop that
    uses it, so a run of layers never copies its weights out of the stack."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, j, keepdims=False), stack)


# ------------------------------------------------------------- geometry --
def layer_runs(cfg: ModelConfig) -> List[Tuple[str, int, int]]:
    """Consecutive layers of one mixer kind: ``(kind, first index among
    that kind's stacked weights, count)`` in layer order."""
    runs: List[Tuple[str, int, int]] = []
    seen = {MAMBA: 0, ATTN: 0}
    for kind in cfg.layer_types:
        if kind not in seen:
            raise ValueError(f"unknown layer type {kind!r}")
        if runs and runs[-1][0] == kind:
            k, start, n = runs[-1]
            runs[-1] = (k, start, n + 1)
        else:
            runs.append((kind, seen[kind], 1))
        seen[kind] += 1
    return runs


def n_layers_of(cfg: ModelConfig, kind: str) -> int:
    return sum(1 for k in cfg.layer_types if k == kind)


def init_state(cfg: ModelConfig, slots: int) -> Dict[str, jax.Array]:
    """The zeroed per-slot recurrent state of the Mamba layers."""
    rows, t = ssm_mod.state_rows(cfg)
    n_m = n_layers_of(cfg, MAMBA)
    return {
        "ssm": jnp.zeros((slots, n_m, rows, cfg.ssm_state_dim, t),
                         jnp.float32),
        "conv": jnp.zeros((slots, n_m, cfg.ssm_conv_width - 1,
                           cfg.ssm_conv_dim), jnp.float32),
    }


# ---------------------------------------------------------------- pieces --
def _ffn(layer: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """``x + rs * (routed share + shared expert)`` of ``rms(x, ln2)``;
    x: (..., D)."""
    shape = x.shape
    h = rms_norm(x, layer["ln2"], cfg.norm_eps).reshape(-1, shape[-1])
    y = moe_mod.moe_share_apply(layer["moe"], h, cfg)
    y = y + ffn_apply(layer["moe"]["shared"], h, gated=True,
                      activation=cfg.activation)
    return x + (cfg.residual_scale * y.astype(jnp.float32)).astype(
        x.dtype).reshape(shape)


def _residual(x: jax.Array, y: jax.Array, cfg: ModelConfig) -> jax.Array:
    return x + (cfg.residual_scale * y.astype(jnp.float32)).astype(x.dtype)


def _qkv(a: Params, h: jax.Array, cfg: ModelConfig):
    hd = cfg.resolved_head_dim
    lead = h.shape[:-1]
    q = (h @ a["wq"]).reshape(*lead, cfg.num_heads, hd)
    k = (h @ a["wk"]).reshape(*lead, cfg.num_kv_heads, hd)
    v = (h @ a["wv"]).reshape(*lead, cfg.num_kv_heads, hd)
    return q, k, v


def _embed(params: Params, tokens: jax.Array, cfg: ModelConfig, act_dt):
    x = embed_lookup(params["embed"], tokens).astype(jnp.float32)
    return (x * cfg.embed_scale).astype(act_dt)


def _logits(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x) / cfg.logits_scale
    return mask_padded_logits_raw(logits, cfg.vocab_size)


# --------------------------------------------------------------- prefill --
def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            opts: RuntimeOptions):
    """Prefill ``(B, S)`` left-padded prompts from an empty state.

    Returns ``(logits (B, S, V), k, v, state)``: the attention layers' K/V
    ``(L_a, B, S, kvh, hd)`` in ``opts.kv_cache_dtype`` for the block
    pool, and the per-row recurrent state ``{"ssm": (B, L_m, R, N, T),
    "conv": (B, L_m, W-1, C)}`` float32."""
    act_dt = dtype_of(cfg.activation_dtype)
    kv_dt = dtype_of(opts.kv_cache_dtype)
    params = cast_params(params, act_dt)
    x = _embed(params, tokens, cfg, act_dt)
    s = x.shape[1]
    _, t = ssm_mod.state_rows(cfg)
    scale = cfg.resolved_attn_scale

    def mamba_layer(x, j):
        layer = _layer_at(params["mamba"], j)
        h = rms_norm(x, layer["ln1"], cfg.norm_eps)
        y, st, cv = ssm_mod.mamba_prefill_states(layer["mixer"], h, cfg)
        x = _ffn(layer, _residual(x, y, cfg), cfg)
        return x, (ssm_mod.heads_to_rows(st, t), cv.astype(jnp.float32))

    ks, vs, ssm, conv = [], [], [], []
    for kind, start, n in layer_runs(cfg):
        if kind == MAMBA:
            x, (st, cv) = jax.lax.scan(
                mamba_layer, x, jnp.arange(start, start + n, dtype=jnp.int32))
            ssm.append(st)
            conv.append(cv)
            continue
        for j in range(start, start + n):
            layer = jax.tree_util.tree_map(lambda a: a[j], params["attn"])
            h = rms_norm(x, layer["ln1"], cfg.norm_eps)
            q, k, v = _qkv(layer["attn"], h, cfg)
            impl = ("chunked" if s > 1024 and s % opts.q_chunk == 0
                    and s % opts.k_chunk == 0 else "full")
            if impl == "chunked":
                o = attn_mod.chunked_attention(
                    q, k, v, causal=True, q_chunk=opts.q_chunk,
                    k_chunk=opts.k_chunk, scale=scale)
            else:
                o = attn_mod.full_attention(q, k, v, causal=True,
                                            scale=scale)
            o = o.reshape(*o.shape[:2], -1) @ layer["attn"]["wo"]
            x = _ffn(layer, _residual(x, o, cfg), cfg)
            ks.append(k.astype(kv_dt))
            vs.append(v.astype(kv_dt))
    state = {"ssm": jnp.moveaxis(jnp.concatenate(ssm), 0, 1),
             "conv": jnp.moveaxis(jnp.concatenate(conv), 0, 1)}
    return _logits(params, x, cfg), jnp.stack(ks), jnp.stack(vs), state


# ---------------------------------------------------------------- decode --
def paged_decode(params: Params, cfg: ModelConfig, slot_cache, pool,
                 tokens: jax.Array, tables: jax.Array, opts: RuntimeOptions):
    """One decode token per slot over the paged pool and the slot state.

    Returns ``(logits (slots, V), rows_k, rows_v, state)``: each attention
    layer's new K/V row ``(slots, L_a, kvh, hd)`` for the caller's scatter
    into the tail blocks, and the updated ``{"ssm", "conv"}`` — the slot
    cache's own buffers, updated in place where the program donates
    them (the TPU kernel aliases the state)."""
    act_dt = dtype_of(cfg.activation_dtype)
    params = cast_params(params, act_dt)
    x = _embed(params, tokens, cfg, act_dt)                   # (slots, D)
    pos = slot_cache["pos"]
    ssm, conv = slot_cache["ssm"], slot_cache["conv"]
    rows, t = ssm_mod.state_rows(cfg)
    slots = x.shape[0]
    di, p = cfg.ssm_d_inner, cfg.ssm_head_dim
    n, gr = cfg.ssm_state_dim, cfg.ssm_ngroups
    has_scales = "k_scale" in pool

    def mamba_layer(carry, j):
        x, ssm, conv = carry
        layer = _layer_at(params["mamba"], j)
        mp = layer["mixer"]
        h = rms_norm(x, layer["ln1"], cfg.norm_eps)
        z, xbc, dt = ssm_mod._split_in_proj(cfg, h @ mp["in_proj"])
        cv = jax.lax.dynamic_index_in_dim(conv, j, axis=1, keepdims=False)
        xbc, cv = causal_conv1d_step(xbc, cv.astype(xbc.dtype), mp["conv_w"],
                                     mp["conv_b"])
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, cv.astype(conv.dtype), j, axis=1)
        xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(act_dt)
        xs_ = xbc[:, :di]
        bc = xbc[:, di:].astype(jnp.float32)
        b = jnp.broadcast_to(bc[:, :gr * n, None], (slots, n, t))
        c = jnp.broadcast_to(bc[:, gr * n:, None], (slots, n, t))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"])
        lanes = (rows, t)
        ssm, y = kernel_ops.mamba_decode(
            ssm, j, xs_.reshape(slots, *lanes),
            ssm_mod.per_lane(dt, p).reshape(slots, *lanes),
            ssm_mod.per_lane(-jnp.exp(mp["a_log"]), p).reshape(lanes),
            ssm_mod.per_lane(mp["d_skip"], p).reshape(lanes), b, c)
        y = gated_rms_norm(y.reshape(slots, di).astype(act_dt), z,
                           mp["norm_scale"], cfg.norm_eps)
        x = _ffn(layer, _residual(x, y @ mp["out_proj"], cfg), cfg)
        return (x, ssm, conv), None

    rks, rvs = [], []
    for kind, start, cnt in layer_runs(cfg):
        if kind == MAMBA:
            idx = jnp.arange(start, start + cnt, dtype=jnp.int32)
            (x, ssm, conv), _ = jax.lax.scan(mamba_layer, (x, ssm, conv),
                                             idx)
            continue
        for j in range(start, start + cnt):
            layer = jax.tree_util.tree_map(lambda a: a[j], params["attn"])
            h = rms_norm(x, layer["ln1"], cfg.norm_eps)
            q, k, v = _qkv(layer["attn"], h, cfg)
            o = kernel_ops.paged_attention(
                q, pool["k"][:, j], pool["v"][:, j], tables, pos, k, v,
                pool["k_scale"][:, j] if has_scales else None,
                pool["v_scale"][:, j] if has_scales else None,
                window=opts.decode_window, scale=cfg.resolved_attn_scale)
            o = o.reshape(slots, -1) @ layer["attn"]["wo"]
            x = _ffn(layer, _residual(x, o, cfg), cfg)
            rks.append(k)
            rvs.append(v)
    return (_logits(params, x, cfg), jnp.stack(rks, axis=1),
            jnp.stack(rvs, axis=1), {"ssm": ssm, "conv": conv})
