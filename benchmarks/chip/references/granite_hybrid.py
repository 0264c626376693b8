"""Plain float32 reference of a Granite-4.0-H stack (``granitemoehybrid``):
each layer a Mamba-2 or a NoPE GQA mixer, then a MoE feed-forward with a
shared expert, as Hugging Face's ``GraniteMoeHybrid`` describes them:

    x   = embed(tokens) * embedding_multiplier
    h   = x + residual_multiplier * mixer(rms(x))
    x'  = h + residual_multiplier * (moe(rms(h)) + shared_mlp(rms(h)))
    logits = rms(x_last) @ embed.T / logits_scaling

* Mamba-2: ``in_proj`` gives ``[z | xBC | dt]``; a causal depthwise conv
  of width ``d_conv`` with bias over xBC, then SiLU; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; per head, step by step,
  ``s_t = exp(dt_t A) s_{t-1} + dt_t x_t (x) B_t`` and ``y_t = s_t C_t +
  D x_t`` (the sequential recurrence, not the chunked form); then
  ``rms(y * silu(z))`` and ``out_proj``.
* Attention: GQA without positional encoding, softmax scale
  ``attention_multiplier``, causal.
* MoE: router logits, the top ``num_experts_per_tok`` of them, a softmax
  over those (GraniteMoe's gating); each expert ``silu(x W_gate) * (x
  W_up) W_down``; the shared expert the same at its own width.

Written from that description and independent of the program: it imports
nothing of it and reads only the benchmark's weights.  Departures: norm
weights are stored as ``scale`` and applied as ``1 + scale``; the routed
experts are the chip's share (``deployment.experts_held``): the router
keeps its 72 outputs and its top-10, and only the held experts add their
part, as the program computes it; the LM head is the tied embedding, as
published.  One layer at a time in float32 at ``highest`` matmul
precision.  Like the program, the context is the left-padded row the
engine prefilled, padding attended.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _int8(w, axis=0):
    """``w`` rounded to int8 (symmetric, one scale along ``axis``)."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.round(w / scale) * scale


def _mm(x, w, int8):
    """``x @ w`` in float32, or with both rounded to int8 (W8A8)."""
    if int8:
        return _int8(x, axis=-1) @ _int8(w)
    return x @ w.astype(jnp.float32)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _moe(x, moe, dims, int8):
    """The held experts' part for every token, plus the shared expert."""
    k, held = dims["k"], jnp.asarray(dims["held"])
    logits = _mm(x, moe["router"], int8)                     # (S, E)
    top_v, top_i = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_v, axis=-1)
    g = jnp.sum(jnp.where(top_i[:, :, None] == held[None, None, :],
                          gates[:, :, None], 0.0), axis=1)   # (S, E_held)
    y = jnp.zeros_like(x)
    for j in range(len(dims["held"])):
        hid = jax.nn.silu(_mm(x, moe["w_gate"][j], int8)) \
            * _mm(x, moe["w_up"][j], int8)
        y = y + g[:, j:j + 1] * _mm(hid, moe["w_down"][j], int8)
    sh = moe["shared"]
    hid = jax.nn.silu(_mm(x, sh["w_gate"], int8)) * _mm(x, sh["w_up"], int8)
    return y + _mm(hid, sh["w_down"], int8)


def _ffn(x, layer, dims, int8):
    h = _rms(x, layer["ln2"], dims["eps"])
    return x + dims["rs"] * _moe(h, layer["moe"], dims, int8)


@partial(jax.jit, static_argnames=("dims", "int8"))
def _mamba_layer(x, layer, dims, int8=False):
    dims = dict(dims)
    mp = layer["mixer"]
    s, _ = x.shape
    di, nh, p, n = dims["di"], dims["nh"], dims["p"], dims["n"]
    h = _rms(x, layer["ln1"], dims["eps"])
    proj = _mm(h, mp["in_proj"], int8)
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * n], proj[:, 2 * di
                                                                + 2 * n:]
    w = mp["conv_w"].astype(jnp.float32)                     # (C, W)
    width = w.shape[1]
    pad = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = sum(pad[i:i + s] * w[:, i] for i in range(width)) \
        + mp["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(s, nh, p)
    b, c = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + mp["dt_bias"].astype(jnp.float32))  # (S, H)
    a = -jnp.exp(mp["a_log"].astype(jnp.float32))

    def step(state, inp):                                    # (H, P, N)
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.einsum("hpn,n->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), jnp.float32),
                        (xs, dt, b, c))
    y = y + mp["d_skip"].astype(jnp.float32)[None, :, None] * xs
    y = _rms(y.reshape(s, di) * jax.nn.silu(z), mp["norm_scale"],
             dims["eps"])
    x = x + dims["rs"] * _mm(y, mp["out_proj"], int8)
    return _ffn(x, layer, dims, int8)


@partial(jax.jit, static_argnames=("dims", "int8"))
def _attn_layer(x, layer, dims, int8=False):
    dims = dict(dims)
    h, kvh, hd = dims["h"], dims["kvh"], dims["hd"]
    s, _ = x.shape
    a = layer["attn"]
    y = _rms(x, layer["ln1"], dims["eps"])
    q = _mm(y, a["wq"], int8).reshape(s, h, hd)
    k = jnp.repeat(_mm(y, a["wk"], int8).reshape(s, kvh, hd), h // kvh, 1)
    v = jnp.repeat(_mm(y, a["wv"], int8).reshape(s, kvh, hd), h // kvh, 1)
    nq = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
    qb = q.reshape(nq, s // nq, h, hd)

    def block(args):
        i, qi = args
        rows = i * (s // nq) + jnp.arange(s // nq)
        sc = jnp.einsum("qhd,khd->hqk", qi, k) * dims["scale"]
        sc = jnp.where(rows[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(block, (jnp.arange(nq), qb)).reshape(s, h * hd)
    x = x + dims["rs"] * _mm(o, a["wo"], int8)
    return _ffn(x, layer, dims, int8)


@partial(jax.jit, static_argnames=("eps", "vocab", "logit_scale", "int8"))
def _head(x, final_norm, embed, tokens, eps, vocab, logit_scale, int8=False):
    """Per position: the best logit, the logit of ``tokens`` (the token
    served after that position) and the best token."""
    y = _rms(x, final_norm, eps)
    emb_t = embed[:vocab].T

    def block(args):
        yi, ti = args
        lg = _mm(yi, emb_t, int8) / logit_scale
        return (lg.max(-1), jnp.take_along_axis(lg, ti[:, None], -1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    s = x.shape[0]
    nq = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
    best, got, arg = jax.lax.map(block, (y.reshape(nq, s // nq, -1),
                                         tokens.reshape(nq, s // nq)))
    return best.reshape(s), got.reshape(s), arg.reshape(s)


def _dims(model: dict) -> tuple:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    di = model["mamba_expand"] * d
    dims = dict(h=h, kvh=model["num_key_value_heads"],
                hd=model.get("head_dim") or d // h, di=di,
                nh=model["mamba_n_heads"], p=di // model["mamba_n_heads"],
                n=model["mamba_d_state"], eps=float(model["rms_norm_eps"]),
                rs=float(model["residual_multiplier"]),
                scale=float(model["attention_multiplier"]),
                k=model["num_experts_per_tok"],
                held=tuple(model["deployment"]["experts_held"]))
    if model["mamba_n_groups"] != 1:
        raise ValueError("the reference reads one group of B and C")
    return tuple(sorted(dims.items()))


def served_gaps(params, model: dict, layers: int, context, served,
                control: bool = False):
    """How far below the reference's best logit each served token lies.

    ``context`` is the token row the program was given (as it padded it)
    and ``served`` the tokens it produced after it, the first from the
    context's last position.  The reference runs the first ``layers``
    layers this chip holds over ``context + served[:-1]``, padded at the
    end to a power of two from the query block (causal, so padding after
    changes nothing before), and returns one gap per served token, in
    logit units.  ``control=True``: the same reference with every matmul
    in int8 (weights per output channel, activations per token) picks the
    token at each position instead, and the gap of that token is
    returned."""
    context = np.asarray(context, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([context, served[:-1]])
    n = len(seq)
    s = Q_BLOCK
    while s < n:
        s *= 2
    toks = np.zeros(s, np.int32)
    toks[:n] = seq
    nxt = np.zeros(s, np.int32)
    nxt[len(context) - 1:n] = served
    if control:
        nxt = np.asarray(_forward(params, model, layers, toks, nxt, True)[2])
    best, got, _ = _forward(params, model, layers, toks, nxt, False)
    sl = slice(len(context) - 1, n)
    return np.asarray(best)[sl] - np.asarray(got)[sl]


def _hidden(params, model, layers, toks, int8):
    """The last layer's output at every position of ``toks``."""
    dims = _dims(model)
    first = model["deployment"]["layers_held"][0]
    types = model["layer_types"][first:first + layers]
    seen = {"mamba": 0, "attention": 0}
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32) \
        * float(model["embedding_multiplier"])
    for kind in types:
        group = "mamba" if kind == "mamba" else "attn"
        layer = jax.tree_util.tree_map(lambda a: a[seen[kind]],
                                       params[group])
        seen[kind] += 1
        fn = _mamba_layer if kind == "mamba" else _attn_layer
        x = fn(x, layer, dims, int8)
    return x


def logits(params, model: dict, layers: int, toks) -> np.ndarray:
    """The reference's logits ``(S, vocab)`` at every position of the
    token row ``toks`` (the tests' full forward)."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, model, layers, np.asarray(toks, np.int32), False)
        y = _rms(x, params["final_norm"], float(model["rms_norm_eps"]))
        emb = params["embed"][:int(model["vocab_size"])].astype(jnp.float32)
        return np.asarray(y @ emb.T / float(model["logits_scaling"]))


def _forward(params, model, layers, toks, nxt, int8):
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, model, layers, toks, int8)
        return _head(x, params["final_norm"], params["embed"],
                     jnp.asarray(nxt), float(model["rms_norm_eps"]),
                     int(model["vocab_size"]),
                     float(model["logits_scaling"]), int8)
