"""Mamba2 (state-space duality) blocks — chunked SSD scan + O(1) decode step.

Follows the SSD formulation of arXiv:2405.21060: within-chunk attention-like
quadratic form + inter-chunk linear recurrence carried by ``lax.scan``.
The pure-jnp path below is the oracle for the Pallas ``ssd_scan`` kernel.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .configs import ModelConfig
from .layers import (Params, causal_conv1d, causal_conv1d_step,
                     gated_rms_norm, rms_norm)


def segsum(x: jax.Array) -> jax.Array:
    """Lower-triangular segment sums: out[..., i, j] = sum_{k=j+1..i} x[..., k]."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    ss = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))
    return jnp.where(mask, ss, -jnp.inf)


def ssd_scan_ref(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                 c: jax.Array, *, chunk: int,
                 initial_state: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD.

    x:  (B, S, H, P)   input (pre-discretization)
    dt: (B, S, H)      positive step sizes (softplus applied by caller)
    a:  (H,)           negative decay rates
    b,c:(B, S, G, N)   input/output projections (G groups broadcast to H)
    Returns (y: (B,S,H,P), final_state: (B,H,P,N)).
    """
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    chunk = min(chunk, s)
    if s % chunk:
        # pad with dt=0 steps: decay exp(0)=1 and zero input leave the
        # carried state untouched, so the final state is exact.
        pad = chunk - s % chunk
        y, final = ssd_scan_ref(
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(dt, ((0, 0), (0, pad), (0, 0))), a,
            jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0))),
            chunk=chunk, initial_state=initial_state)
        return y[:, :s], final
    nc = s // chunk
    rep = h // g

    xd = (x * dt[..., None]).astype(jnp.float32)         # discretized input
    bh = jnp.repeat(b, rep, axis=2).astype(jnp.float32)  # (B,S,H,N)
    ch = jnp.repeat(c, rep, axis=2).astype(jnp.float32)

    xb = xd.reshape(bsz, nc, chunk, h, p)
    bb = bh.reshape(bsz, nc, chunk, h, n)
    cb = ch.reshape(bsz, nc, chunk, h, n)
    da = (dt.astype(jnp.float32) * a.astype(jnp.float32)).reshape(bsz, nc, chunk, h)
    da = jnp.moveaxis(da, -1, -2)                        # (B, nc, H, L)
    da_cs = jnp.cumsum(da, axis=-1)                      # (B, nc, H, L)

    # --- intra-chunk (diagonal blocks) ---
    decay = jnp.exp(segsum(da))                          # (B, nc, H, L, L)
    y_diag = jnp.einsum("bclhn,bcshn,bchls,bcshp->bclhp", cb, bb, decay, xb)

    # --- chunk-final states ---
    decay_states = jnp.exp(da_cs[..., -1:] - da_cs)      # (B, nc, H, L)
    states = jnp.einsum("bclhn,bchl,bclhp->bchpn", bb, decay_states, xb)

    # --- inter-chunk recurrence ---
    chunk_decay = jnp.exp(da_cs[..., -1])                # (B, nc, H)
    if initial_state is None:
        initial_state = jnp.zeros((bsz, h, p, n), jnp.float32)

    def step(prev, inp):
        st, dec = inp                                    # (B,H,P,N), (B,H)
        new = prev * dec[..., None, None] + st
        return new, prev                                 # emit state *entering* chunk

    final, prev_states = jax.lax.scan(
        step, initial_state.astype(jnp.float32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)        # (B, nc, H, P, N)

    # --- contribution of the carried state ---
    state_decay = jnp.exp(da_cs)                         # (B, nc, H, L)
    y_off = jnp.einsum("bclhn,bchpn,bchl->bclhp", cb, prev_states, state_decay)

    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y.astype(x.dtype), final


def ssd_step(state: jax.Array, x_t: jax.Array, dt_t: jax.Array, a: jax.Array,
             b_t: jax.Array, c_t: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single decode step of the SSD recurrence.

    state: (B,H,P,N); x_t: (B,H,P); dt_t: (B,H); b_t,c_t: (B,G,N).
    """
    h = x_t.shape[1]
    g = b_t.shape[1]
    rep = h // g
    bh = jnp.repeat(b_t, rep, axis=1).astype(jnp.float32)   # (B,H,N)
    chh = jnp.repeat(c_t, rep, axis=1).astype(jnp.float32)
    da = jnp.exp(dt_t.astype(jnp.float32) * a.astype(jnp.float32))  # (B,H)
    xd = (x_t * dt_t[..., None]).astype(jnp.float32)
    state = state * da[..., None, None] + xd[..., None] * bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", state, chh)
    return y.astype(x_t.dtype), state


# ------------------------------------------------------------------ block ---
def mamba_init(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    d, di = cfg.d_model, cfg.ssm_d_inner
    nh, st, gr = cfg.ssm_num_heads, cfg.ssm_state_dim, cfg.ssm_ngroups
    conv_dim, w = cfg.ssm_conv_dim, cfg.ssm_conv_width
    ks = jax.random.split(key, 4)
    in_dim = 2 * di + 2 * gr * st + nh
    p = {
        "in_proj": (jax.random.normal(ks[0], (d, in_dim)) / np.sqrt(d)).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (conv_dim, w)) / np.sqrt(w)).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_proj": (jax.random.normal(ks[2], (di, d)) / np.sqrt(di)).astype(dtype),
        "norm_scale": jnp.zeros((di,), dtype),
    }
    return p


def _split_in_proj(cfg: ModelConfig, proj: jax.Array):
    di, gr, st, nh = (cfg.ssm_d_inner, cfg.ssm_ngroups, cfg.ssm_state_dim,
                      cfg.ssm_num_heads)
    z = proj[..., :di]
    xbc = proj[..., di:di + cfg.ssm_conv_dim]
    dt = proj[..., di + cfg.ssm_conv_dim:]
    return z, xbc, dt


def mamba_forward(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Full-sequence Mamba2 block.  x: (B, S, D) -> (B, S, D)."""
    bsz, s, _ = x.shape
    di, nh, hd = cfg.ssm_d_inner, cfg.ssm_num_heads, cfg.ssm_head_dim
    gr, st = cfg.ssm_ngroups, cfg.ssm_state_dim
    proj = x @ params["in_proj"]
    z, xbc, dt = _split_in_proj(cfg, proj)
    xbc = jax.nn.silu(causal_conv1d(xbc, params["conv_w"], params["conv_b"])
                      .astype(jnp.float32)).astype(x.dtype)
    xs = xbc[..., :di].reshape(bsz, s, nh, hd)
    b = xbc[..., di:di + gr * st].reshape(bsz, s, gr, st)
    c = xbc[..., di + gr * st:].reshape(bsz, s, gr, st)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    y, _ = ssd_scan_ref(xs, dt, a, b, c, chunk=cfg.ssm_chunk)
    y = y + params["d_skip"][None, None, :, None] * xs
    y = y.reshape(bsz, s, di)
    y = gated_rms_norm(y, z, params["norm_scale"], cfg.norm_eps)
    return y @ params["out_proj"]


def mamba_step(params: Params, x_t: jax.Array, ssm_state: jax.Array,
               conv_state: jax.Array, cfg: ModelConfig
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step.  x_t: (B, D); ssm_state: (B,H,P,N);
    conv_state: (B, W-1, conv_dim)."""
    bsz = x_t.shape[0]
    di, nh, hd = cfg.ssm_d_inner, cfg.ssm_num_heads, cfg.ssm_head_dim
    gr, st = cfg.ssm_ngroups, cfg.ssm_state_dim
    proj = x_t @ params["in_proj"]
    z, xbc, dt = _split_in_proj(cfg, proj)
    xbc, conv_state = causal_conv1d_step(xbc, conv_state, params["conv_w"],
                                         params["conv_b"])
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(x_t.dtype)
    xs = xbc[..., :di].reshape(bsz, nh, hd)
    b = xbc[..., di:di + gr * st].reshape(bsz, gr, st)
    c = xbc[..., di + gr * st:].reshape(bsz, gr, st)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    y, ssm_state = ssd_step(ssm_state, xs, dt, a, b, c)
    y = y + params["d_skip"][None, :, None] * xs
    y = y.reshape(bsz, di)
    y = gated_rms_norm(y, z, params["norm_scale"], cfg.norm_eps)
    return y @ params["out_proj"], ssm_state, conv_state


def mamba_prefill_states(mp: Params, h: jax.Array, cfg: ModelConfig):
    """Mamba block forward over a prompt that also returns its final SSM
    state ``(B, H, P, N)`` f32 and conv state ``(B, W-1, conv_dim)`` (the
    last ``W-1`` pre-conv inputs), in ``h.dtype``."""
    bsz, s, _ = h.shape
    di, nh, hdim = cfg.ssm_d_inner, cfg.ssm_num_heads, cfg.ssm_head_dim
    gr, st = cfg.ssm_ngroups, cfg.ssm_state_dim
    proj = h @ mp["in_proj"]
    z, xbc_pre, dt = _split_in_proj(cfg, proj)
    conv_state = xbc_pre[:, -(cfg.ssm_conv_width - 1):, :]
    xbc = jax.nn.silu(causal_conv1d(xbc_pre, mp["conv_w"], mp["conv_b"])
                      .astype(jnp.float32)).astype(h.dtype)
    xs = xbc[..., :di].reshape(bsz, s, nh, hdim)
    bmat = xbc[..., di:di + gr * st].reshape(bsz, s, gr, st)
    cmat = xbc[..., di + gr * st:].reshape(bsz, s, gr, st)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"])
    a = -jnp.exp(mp["a_log"])
    y, final_state = ssd_scan_ref(xs, dt, a, bmat, cmat, chunk=cfg.ssm_chunk)
    y = y + mp["d_skip"][None, None, :, None] * xs
    y = y.reshape(bsz, s, di)
    y = gated_rms_norm(y, z, mp["norm_scale"], cfg.norm_eps)
    return y @ mp["out_proj"], final_state, conv_state


# ------------------------------------------------ paged (row) state layout --
# The paged serving path keeps each slot's SSM state as float32 rows of T
# lanes, ``(R, N, T)`` per layer (``repro.kernels.mamba_decode``): row r
# holds heads ``r*T//P .. (r+1)*T//P - 1``, its lanes their P channels.

def state_rows(cfg: ModelConfig) -> Tuple[int, int]:
    """``(R, T)`` of the row layout: ``T = min(128, H*P)`` lanes a row."""
    width = cfg.ssm_num_heads * cfg.ssm_head_dim
    t = min(128, width)
    if t % cfg.ssm_head_dim or width % t or cfg.ssm_ngroups != 1:
        raise ValueError(
            f"paged SSM state needs one group and heads that tile rows of "
            f"{t} lanes (heads {cfg.ssm_num_heads} x {cfg.ssm_head_dim}, "
            f"groups {cfg.ssm_ngroups})")
    return width // t, t


def heads_to_rows(state: jax.Array, t: int) -> jax.Array:
    """``(..., H, P, N)`` -> ``(..., H*P//T, N, T)``."""
    *lead, h, p, n = state.shape
    a = state.reshape(*lead, h * p // t, t // p, p, n)
    a = jnp.moveaxis(a, -1, -3)                       # (..., R, N, T//P, P)
    return a.reshape(*lead, h * p // t, n, t)


def per_lane(v: jax.Array, head_dim: int) -> jax.Array:
    """A per-head ``(..., H)`` value repeated over each head's channels:
    ``(..., H * head_dim)``."""
    return jnp.repeat(v, head_dim, axis=-1)


def mamba_state_shapes(cfg: ModelConfig, batch: int):
    return (
        (batch, cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_dim),
        (batch, cfg.ssm_conv_width - 1, cfg.ssm_conv_dim),
    )
