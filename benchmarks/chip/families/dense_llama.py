"""The dense Llama-style family (Llama, Yi, the paper backbone): the
program's configuration object for a configuration file, random weights,
and the FLOP and byte counts of its tokens.

A configuration file names its family (``"family": "dense_llama"``) and
its reference (``references/<name>.py``); the harness finds both by name,
so a configuration of another architecture brings files of its own.

Weights are made on the device from the seed in one jitted call, in the
dtype they are served in, laid out the way the program takes them
(stacked layers: ``embed``, ``final_norm``, ``layers/{ln1, attn/{wq, wk,
wv, wo}, ln2, ffn/{w_gate, w_up, w_down}}``).  The reference reads the
same arrays: the benchmark makes them, not the program.  Norm weights are
``1 + scale`` with ``scale`` drawn small and non-zero, so the norms do
real work.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import flops

# FLOPs and bytes of this family's tokens (see flops.py)
token_flops = flops.token_flops
prompt_flops = flops.prompt_flops
paged_attn_work = flops.paged_attn_work

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, 64 bits and beyond 32."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def padded_vocab(model: dict) -> int:
    return (model["vocab_size"] + 255) // 256 * 256


@partial(jax.jit, static_argnums=(1,))
def _make(key, key_of_shapes):
    (layers, d, h, kvh, hd, f, vp, dtype_name) = key_of_shapes
    dt = DTYPES[dtype_name]
    ks = iter(jax.random.split(key, 12))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dt)

    return {
        "embed": normal((vp, d), 0.02),
        "final_norm": normal((d,), 0.1),
        "layers": {
            "ln1": normal((layers, d), 0.1),
            "ln2": normal((layers, d), 0.1),
            "attn": {"wq": normal((layers, d, h * hd), 1 / np.sqrt(d)),
                     "wk": normal((layers, d, kvh * hd), 1 / np.sqrt(d)),
                     "wv": normal((layers, d, kvh * hd), 1 / np.sqrt(d)),
                     "wo": normal((layers, h * hd, d), 1 / np.sqrt(h * hd))},
            "ffn": {"w_gate": normal((layers, d, f), 1 / np.sqrt(d)),
                    "w_up": normal((layers, d, f), 1 / np.sqrt(d)),
                    "w_down": normal((layers, f, d), 1 / np.sqrt(f))},
        },
    }


def model_config(model: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.configs import ModelConfig
    d, h = model["hidden_size"], model["num_attention_heads"]
    return ModelConfig(
        name=model["name"], arch_type="dense",
        num_layers=model["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim") or d // h,
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        gated_ffn=True, activation=model["hidden_act"],
        tie_embeddings=model["tie_word_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        param_dtype=model["torch_dtype"],
        activation_dtype=model["compute_dtype"],
        max_seq_len=model["max_position_embeddings"],
        source=model.get("source", ""))


def shape_key(model: dict, layers: int = 0) -> tuple:
    """What fixes the weights' shapes and dtype (``layers`` overrides the
    configuration's depth)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    return (layers or model["num_hidden_layers"], d, h,
            model["num_key_value_heads"], model.get("head_dim") or d // h,
            model["intermediate_size"], padded_vocab(model),
            model["torch_dtype"])


def make_params(model: dict, seed: int):
    """The weights of ``model`` (a configuration file's dict) from
    ``seed``, on the default device."""
    params = _make(seed_key(seed), shape_key(model))
    jax.block_until_ready(params)
    return params
