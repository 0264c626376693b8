"""Plain float32 reference of a dense Llama-style decoder (Llama, Yi and the
paper backbone share it): RMSNorm, rotary position embedding (rotate-half,
``theta ** (-i / half)``), grouped-query causal attention, SwiGLU FFN and
a tied LM head.

Written from the published description and independent of the program:
it imports nothing of it and reads only the benchmark's weights.  It runs
one layer at a time in float32 at ``highest`` matmul precision, with
attention over blocks of queries, so that it fits beside the weights.
Departures from the published Yi-34B: the LM head is tied to the
embedding (the configuration lists it under ``reduced``), and norm weights
are stored as ``scale`` and applied as ``1 + scale``.

The serving engine left-pads a prompt to its bucket with token 0 and
attends to the padding, the real tokens at positions shifted by it.  The
reference is given the padded row and attends to it too, on purpose: it
computes what the program is meant to compute for that row, so the check
judges the kernels and not the engine's padding rule.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _int8(w, axis=0):
    """``w`` rounded to int8 (symmetric, one scale along ``axis``: per
    output channel of a weight, per token of an activation) and back."""
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


def _rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "int8"))
def _layer(x, layer, dims, int8=False):
    h, kvh, hd, eps, theta = dims
    s, d = x.shape
    a = layer["attn"]
    y = _rms(x, layer["ln1"], eps)
    q = _rope(_mm(y, a["wq"], int8).reshape(s, h, hd), theta)
    k = _rope(_mm(y, a["wk"], int8).reshape(s, kvh, hd), theta)
    v = _mm(y, a["wv"], int8).reshape(s, kvh, hd)
    g = h // kvh
    kk = jnp.repeat(k, g, axis=1)                       # (S, H, hd)
    vv = jnp.repeat(v, g, axis=1)
    nq = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
    qb = q.reshape(nq, s // nq, h, hd)

    def block(args):
        i, qi = args
        rows = i * (s // nq) + jnp.arange(s // nq)
        sc = jnp.einsum("qhd,khd->hqk", qi, kk) / np.sqrt(hd)
        mask = rows[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vv)

    o = jax.lax.map(block, (jnp.arange(nq), qb)).reshape(s, h * hd)
    x = x + _mm(o, a["wo"], int8)
    y = _rms(x, layer["ln2"], eps)
    ff = layer["ffn"]
    hid = jax.nn.silu(_mm(y, ff["w_gate"], int8)) * _mm(y, ff["w_up"], int8)
    return x + _mm(hid, ff["w_down"], int8)


@partial(jax.jit, static_argnames=("eps", "vocab", "int8"))
def _head(x, final_norm, embed, tokens, eps, vocab, int8=False):
    """Per position: the best logit, the logit of ``tokens`` (the token
    served after that position) and the best token."""
    y = _rms(x, final_norm, eps)
    emb_t = embed[:vocab].T

    def block(args):
        yi, ti = args
        lg = _mm(yi, emb_t, int8)
        return (lg.max(-1), jnp.take_along_axis(lg, ti[:, None], -1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    s = x.shape[0]
    nq = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
    best, got, arg = jax.lax.map(block, (y.reshape(nq, s // nq, -1),
                                         tokens.reshape(nq, s // nq)))
    return best.reshape(s), got.reshape(s), arg.reshape(s)


def served_gaps(params, model: dict, layers: int, context, served,
                control: bool = False):
    """How far below the reference's best logit each served token lies.

    ``context`` is the token row the program was given (as it padded it)
    and ``served`` the tokens it produced after it, the first from the
    context's last position.  The reference runs the first ``layers``
    layers over ``context + served[:-1]``, padded at the end to a power
    of two from the query block (causal, so padding after changes nothing
    before), and returns one gap per served token, in logit units.

    ``control=True`` is the check's control: the same reference with every
    matmul in int8 (weights per output channel, activations per token)
    picks the token at each position instead, and the gap of that token
    is returned."""
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


def _forward(params, model, layers, toks, nxt, int8):
    d, h = model["hidden_size"], model["num_attention_heads"]
    dims = (h, model["num_key_value_heads"], model.get("head_dim") or d // h,
            float(model["rms_norm_eps"]), float(model["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for j in range(layers):
            layer = jax.tree_util.tree_map(lambda a: a[j], params["layers"])
            x = _layer(x, layer, dims, int8)
        return _head(x, params["final_norm"], params["embed"],
                     jnp.asarray(nxt), float(model["rms_norm_eps"]),
                     int(model["vocab_size"]), int8)
