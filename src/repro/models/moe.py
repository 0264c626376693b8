"""Mixture-of-Experts FFN with token-choice routing and capacity dispatch.

Dispatch strategy (TPU adaptation of the paper's "offloading" all-to-all):
per-expert top-C token *gather* + batched expert matmul + scatter-combine.
Expert weights are stacked (E, D, F) and sharded over the "model" mesh axis
(expert parallelism); the gather/scatter pair is what SPMD lowers to the
all-to-all exchange.  Compute cost is k·T·FFN (capacity factor 1.0), not
E·T·FFN — tokens beyond capacity are dropped Switch-style.

:func:`moe_share_apply` is the served layer of a hybrid MoE stack: told
which experts this chip holds, it routes over all of them and computes
its own experts' part, dropping no token.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .configs import ModelConfig
from .layers import Params, ffn_apply, ffn_init


def moe_init(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(dtype),
    }
    if cfg.moe_shared_expert:
        p["shared"] = ffn_init(ks[4], d, f, gated=cfg.gated_ffn, dtype=dtype)
    return p


def _capacity(num_tokens: int, cfg: ModelConfig, cap_factor: float) -> int:
    c = int(cap_factor * cfg.experts_per_token * num_tokens / cfg.num_experts)
    c = max(8, int(np.ceil(c / 8) * 8))
    return min(c, num_tokens)


def moe_apply(params: Params, x: jax.Array, cfg: ModelConfig, *,
              capacity_factor: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss).

    Routing: softmax router -> per-token top-k gates -> per-expert top-C
    token selection (ties broken by router prob).
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    cap = _capacity(t, cfg, capacity_factor)
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ params["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_i = jax.lax.top_k(probs, k)                      # (T, k)
    # renormalize the selected gates
    topk_p = topk_p / jnp.maximum(jnp.sum(topk_p, -1, keepdims=True), 1e-9)
    # gate matrix restricted to the chosen experts: (T, E)
    gates = jax.nn.one_hot(topk_i, e, dtype=jnp.float32) * topk_p[..., None]
    gates = jnp.sum(gates, axis=1)                                # (T, E)

    # per-expert capacity-C token selection
    scores = jnp.where(gates > 0, gates, -1.0).T                  # (E, T)
    sel_score, sel_idx = jax.lax.top_k(scores, cap)               # (E, C)
    valid = sel_score > 0                                         # dropped slots

    xg = jnp.take(xf, sel_idx.reshape(-1), axis=0)                # (E*C, D)
    xg = xg.reshape(e, cap, d) * valid[..., None].astype(x.dtype)

    h_gate = jnp.einsum("ecd,edf->ecf", xg, params["w_gate"])
    h_up = jnp.einsum("ecd,edf->ecf", xg, params["w_up"])
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.activation]
    h = act(h_gate) * h_up if cfg.gated_ffn else act(h_up)
    yg = jnp.einsum("ecf,efd->ecd", h, params["w_down"])          # (E, C, D)

    gate_sel = jnp.take_along_axis(gates.T, sel_idx, axis=1)      # (E, C)
    yg = yg * (gate_sel * valid).astype(yg.dtype)[..., None]
    y = jnp.zeros((t, d), yg.dtype).at[sel_idx.reshape(-1)].add(
        yg.reshape(-1, d))

    if cfg.moe_shared_expert:
        y = y + ffn_apply(params["shared"], xf, gated=cfg.gated_ffn,
                          activation=cfg.activation)

    # Switch-style load-balance auxiliary loss
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean((gates > 0).astype(jnp.float32), axis=0)         # (E,)
    aux = e * jnp.sum(me * ce)
    return y.reshape(b, s, d).astype(x.dtype), aux


def moe_apply_decode(params: Params, x: jax.Array, cfg: ModelConfig
                     ) -> jax.Array:
    """Decode path: DENSE dispatch.

    The decode batch is tiny (T = global_batch tokens), so every expert
    computes all tokens and a top-k one-hot gate combines the results.
    This is k/E more FLOPs — negligible at decode utilization — but
    expert weights NEVER move: under expert parallelism each shard runs
    its resident experts and the combine is one small (T, D) all-reduce.
    (§Perf: replaced a per-token expert-weight gather that moved
    k·3·D·F·T bytes across the mesh per layer.)"""
    t, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_i = jax.lax.top_k(probs, k)
    topk_p = topk_p / jnp.maximum(jnp.sum(topk_p, -1, keepdims=True), 1e-9)
    gates = jnp.sum(jax.nn.one_hot(topk_i, e, dtype=jnp.float32)
                    * topk_p[..., None], axis=1)                   # (T, E)

    hg = jnp.einsum("td,edf->tef", x, params["w_gate"])
    hu = jnp.einsum("td,edf->tef", x, params["w_up"])
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.activation]
    h = act(hg) * hu if cfg.gated_ffn else act(hu)
    y = jnp.einsum("tef,efd,te->td", h, params["w_down"],
                   gates.astype(h.dtype))
    if cfg.moe_shared_expert:
        y = y + ffn_apply(params["shared"], x, gated=cfg.gated_ffn,
                          activation=cfg.activation)
    return y.astype(x.dtype)


def moe_share_apply(params: Params, x: jax.Array, cfg: ModelConfig
                    ) -> jax.Array:
    """The routed part of a MoE layer that holds only some of its experts
    (expert parallelism, one chip's share), with no token dropped.

    x: (T, D).  The router keeps its published width: ``params["router"]``
    is ``(D, cfg.num_experts)``, each token takes the top
    ``experts_per_token`` logits and a softmax over those (GraniteMoe's
    gating).  ``params["w_gate" | "w_up" | "w_down"]`` stack the experts
    this chip holds, ``cfg.experts_held`` in order, and each computes
    every token with that token's gate for it (0 where the token did not
    route to it), so the result is exactly what these experts add; the
    other chips' experts add the rest.  Dense over the held experts, as
    :func:`moe_apply_decode`: at decode the weights bound the step, and a
    prefill drops nothing.  The caller adds any shared expert once."""
    k = cfg.experts_per_token
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    top_v, top_i = jax.lax.top_k(logits, k)                       # (T, k)
    gates = jax.nn.softmax(top_v, axis=-1)
    held = jnp.asarray(cfg.experts_held, jnp.int32)               # (E_h,)
    g = jnp.sum(jnp.where(top_i[:, :, None] == held[None, None, :],
                          gates[:, :, None], 0.0), axis=1)        # (T, E_h)
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.activation]
    hg = jnp.einsum("td,edf->tef", x, params["w_gate"])
    hu = jnp.einsum("td,edf->tef", x, params["w_up"])
    h = (act(hg.astype(jnp.float32)) * hu.astype(jnp.float32)
         * g[:, :, None]).astype(x.dtype)
    return jnp.einsum("tef,efd->td", h, params["w_down"])
