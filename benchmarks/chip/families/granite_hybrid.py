"""The Granite-4.0-H family (``granitemoehybrid``: Mamba-2 and NoPE GQA
mixers, each followed by a MoE feed-forward with a shared expert): the
program's configuration object for a configuration file, random weights
in the program's layout, and the FLOP and byte counts of its tokens.

A configuration file holds the published ``config.json`` keys, cut as its
``reduced`` says, and a ``deployment`` object naming the layers this chip
runs (``layers_held``: the first of ``num_hidden_layers`` consecutive
layers of the published ``layer_types``), the router's published width
(``router_experts``) and the experts held here (``experts_held``).

Weights are made on the device from the seed in one jitted call, in the
dtype they are served in, laid out as :mod:`repro.models.hybrid_moe` takes
them (weights stacked per mixer kind, in layer order).  The reference
reads the same arrays.  The scales are a seeded stand-in for a trained
stack: norm weights ``1 + scale`` with ``scale`` small and non-zero;
``A_log``, ``dt_bias`` and ``D`` drawn as Mamba-2 initializes them (``A``
in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1], ``D`` = 1); and the
output projections of every branch (``out_proj``, ``wo``, the experts'
and the shared expert's ``w_down``) at ``BRANCH_GAIN`` over ``1/sqrt(fan
in)``, so that each branch, scaled by the published 0.22, moves the
residual stream as a trained stack's do, instead of vanishing beside the
embedding's published x12 (which would make every token's best logit its
own, through the tied head).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import flops

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
BRANCH_GAIN = 16.0
MAMBA, ATTN = "mamba", "attention"


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, 64 bits and beyond 32."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def held_types(model: dict, layers: int = 0) -> list:
    """The mixer kinds of the layers this chip runs, in order."""
    first = model["deployment"]["layers_held"][0]
    n = layers or model["num_hidden_layers"]
    return model["layer_types"][first:first + n]


def _dims(model: dict) -> dict:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    di = model["mamba_expand"] * d
    p = model["mamba_d_head"]
    assert di // p == model["mamba_n_heads"], (di, p, model["mamba_n_heads"])
    return dict(d=d, h=h, kvh=model["num_key_value_heads"],
                hd=model.get("head_dim") or d // h,
                f=model["intermediate_size"],
                fs=model["shared_intermediate_size"], v=model["vocab_size"],
                di=di, nh=di // p, p=p, n=model["mamba_d_state"],
                g=model["mamba_n_groups"], w=model["mamba_d_conv"],
                e=model["deployment"]["router_experts"],
                eh=len(model["deployment"]["experts_held"]),
                k=model["num_experts_per_tok"])


def padded_vocab(model: dict) -> int:
    return (model["vocab_size"] + 255) // 256 * 256


def model_config(model: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.configs import ATTN as P_ATTN, MAMBA as P_MAMBA
    from repro.models.configs import ModelConfig
    m = _dims(model)
    kinds = {MAMBA: P_MAMBA, ATTN: P_ATTN}
    if model["position_embedding_type"] != "nope":
        raise ValueError("the family serves NoPE attention only")
    return ModelConfig(
        name=model["name"], arch_type="hybrid_moe",
        num_layers=model["num_hidden_layers"], d_model=m["d"],
        num_heads=m["h"], num_kv_heads=m["kvh"], head_dim=m["hd"],
        d_ff=m["f"], vocab_size=m["v"], gated_ffn=True,
        activation=model["hidden_act"],
        tie_embeddings=model["tie_word_embeddings"],
        num_experts=m["e"], experts_per_token=m["k"],
        experts_held=tuple(model["deployment"]["experts_held"]),
        shared_expert_d_ff=m["fs"],
        ssm_state_dim=m["n"], ssm_expand=model["mamba_expand"],
        ssm_head_dim=m["p"], ssm_conv_width=m["w"],
        ssm_chunk=model["mamba_chunk_size"], ssm_ngroups=m["g"],
        layer_types=tuple(kinds[t] for t in held_types(model)),
        attn_scale=float(model["attention_multiplier"]),
        embed_scale=float(model["embedding_multiplier"]),
        residual_scale=float(model["residual_multiplier"]),
        logits_scale=float(model["logits_scaling"]),
        norm_eps=float(model["rms_norm_eps"]),
        param_dtype=model["torch_dtype"],
        activation_dtype=model["compute_dtype"],
        max_seq_len=model["max_position_embeddings"],
        source=model.get("source", ""))


def shape_key(model: dict, layers: int = 0) -> tuple:
    """What fixes the weights' shapes and dtype (``layers`` overrides the
    configuration's depth)."""
    m = _dims(model)
    types = held_types(model, layers)
    return (types.count(MAMBA), types.count(ATTN), m["d"], m["h"], m["kvh"],
            m["hd"], m["f"], m["fs"], m["e"], m["eh"], m["di"], m["nh"],
            m["n"], m["g"], m["w"], padded_vocab(model), model["torch_dtype"])


@partial(jax.jit, static_argnums=(1,))
def _make(key, key_of_shapes):
    (n_m, n_a, d, h, kvh, hd, f, fs, e, eh, di, nh, n, g, w, vp,
     dtype_name) = key_of_shapes
    dt = DTYPES[dtype_name]
    f32 = jnp.float32
    ks = iter(jax.random.split(key, 40))
    conv = di + 2 * g * n

    def normal(shape, scale, dtype=dt):
        return (jax.random.normal(next(ks), shape, f32) * scale).astype(dtype)

    def moe(nl):
        return {"router": normal((nl, d, e), 1 / np.sqrt(d)),
                "w_gate": normal((nl, eh, d, f), 1 / np.sqrt(d)),
                "w_up": normal((nl, eh, d, f), 1 / np.sqrt(d)),
                "w_down": normal((nl, eh, f, d), BRANCH_GAIN / np.sqrt(f)),
                "shared": {"w_gate": normal((nl, d, fs), 1 / np.sqrt(d)),
                           "w_up": normal((nl, d, fs), 1 / np.sqrt(d)),
                           "w_down": normal((nl, fs, d),
                                            BRANCH_GAIN / np.sqrt(fs))}}

    # Mamba-2's initialization: dt log-uniform in [1e-3, 1e-1] through
    # softplus (dt_bias its inverse), A uniform in [1, 16], D = 1
    dt0 = jnp.exp(jax.random.uniform(next(ks), (n_m, nh), f32,
                                     np.log(1e-3), np.log(1e-1)))
    return {
        "embed": normal((vp, d), 0.02),
        "final_norm": normal((d,), 0.1),
        "mamba": {
            "ln1": normal((n_m, d), 0.1), "ln2": normal((n_m, d), 0.1),
            "mixer": {
                "in_proj": normal((n_m, d, 2 * di + 2 * g * n + nh),
                                  1 / np.sqrt(d)),
                "conv_w": normal((n_m, conv, w), 1 / np.sqrt(w)),
                "conv_b": normal((n_m, conv), 0.1),
                "a_log": jnp.log(jax.random.uniform(
                    next(ks), (n_m, nh), f32, 1.0, 16.0)),
                "d_skip": jnp.ones((n_m, nh), f32),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "norm_scale": normal((n_m, di), 0.1),
                "out_proj": normal((n_m, di, d), BRANCH_GAIN / np.sqrt(di)),
            },
            "moe": moe(n_m),
        },
        "attn": {
            "ln1": normal((n_a, d), 0.1), "ln2": normal((n_a, d), 0.1),
            "attn": {"wq": normal((n_a, d, h * hd), 1 / np.sqrt(d)),
                     "wk": normal((n_a, d, kvh * hd), 1 / np.sqrt(d)),
                     "wv": normal((n_a, d, kvh * hd), 1 / np.sqrt(d)),
                     "wo": normal((n_a, h * hd, d),
                                  BRANCH_GAIN / np.sqrt(h * hd))},
            "moe": moe(n_a),
        },
    }


def make_params(model: dict, seed: int):
    """The weights of ``model`` (a configuration file's dict) from
    ``seed``, on the default device."""
    params = _make(seed_key(seed), shape_key(model))
    jax.block_until_ready(params)
    return params


# ----------------------------------------------------- FLOPs and bytes --
def _counts(model: dict, layers: int):
    types = held_types(model, layers)
    return types.count(MAMBA), types.count(ATTN)


def _attn_model(model: dict) -> dict:
    """The attention layers' shapes under the dense family's key names."""
    return dict(model, intermediate_size=0)


def dense_flops_per_token(model: dict, layers: int) -> float:
    """Matmul FLOPs of one token through ``layers`` layers and the tied
    head, this chip's share: each mixer's projections (and a Mamba
    layer's state update, ``4 * H * P * N``), the router, the shared
    expert, and the routed experts held here weighted by the share of
    a token's top-k that lands on them (``k * held / router width``).
    Attention over the context is :func:`attn_flops`."""
    m = _dims(model)
    n_m, n_a = _counts(model, layers)
    d = m["d"]
    mamba = (d * (2 * m["di"] + 2 * m["g"] * m["n"] + m["nh"])
             + m["di"] * d + 2 * m["nh"] * m["p"] * m["n"])
    attn = d * m["h"] * m["hd"] + 2 * d * m["kvh"] * m["hd"] \
        + m["h"] * m["hd"] * d
    moe = (d * m["e"] + 3 * d * m["fs"]
           + m["k"] * m["eh"] / m["e"] * 3 * d * m["f"])
    return 2.0 * (n_m * mamba + n_a * attn + (n_m + n_a) * moe
                  + d * m["v"])


def attn_flops(model: dict, layers: int, ctx: int) -> float:
    _, n_a = _counts(model, layers)
    return flops.attn_flops(_attn_model(model), n_a, ctx)


def token_flops(model: dict, layers: int, ctx: int) -> float:
    """Model FLOPs of one decode token that attends over ``ctx`` keys."""
    return dense_flops_per_token(model, layers) + attn_flops(model, layers,
                                                             ctx)


def prompt_flops(model: dict, layers: int, n: int) -> float:
    """Model FLOPs of prefilling ``n`` tokens (causal attention: token
    ``i`` sees ``i + 1`` keys; the recurrence as ``n`` state updates)."""
    m = _dims(model)
    _, n_a = _counts(model, layers)
    causal_keys = n * (n + 1) / 2
    return (n * dense_flops_per_token(model, layers)
            + 4.0 * n_a * m["h"] * m["hd"] * causal_keys)


def paged_attn_work(model: dict, layers: int, pos: int,
                    kv_bytes: int = 2, act_bytes: int = 2):
    """The paged decode attention's needed work for one slot at ``pos``
    cached rows over the attention layers among ``layers`` (the dense
    family's count, :func:`flops.paged_attn_work`)."""
    _, n_a = _counts(model, layers)
    return flops.paged_attn_work(_attn_model(model), n_a, pos, kv_bytes,
                                 act_bytes)


def mamba_decode_work(model: dict, layers: int, act_bytes: int = 2):
    """Needed work of the Mamba decode kernel (``mamba_decode_step``) for
    one live slot over the Mamba layers among ``layers``: ``(flops,
    bytes)``.  Bytes: the slot's float32 SSM state read and written once
    a layer, and the update's inputs and output: ``x`` (activations),
    ``dt`` and ``y`` (float32, one a channel), ``B`` and ``C`` (float32,
    ``N`` each: the kernel takes them broadcast over a row's lanes, but
    that copy is not needed work), ``A`` and ``D`` (float32, one a
    channel, read once per slot).  FLOPs: the update and the readout,
    ``6 * H * P * N``.  The conv state is updated outside the kernel and
    is not counted."""
    m = _dims(model)
    n_m, _ = _counts(model, layers)
    ch = m["nh"] * m["p"]                     # channels, H * P
    state = ch * m["n"] * 4
    io = ch * act_bytes + 2 * ch * 4 + 2 * m["n"] * 4 + 2 * ch * 4
    return n_m * 6.0 * ch * m["n"], n_m * float(2 * state + io)
