"""A Granite-4.0-H-shaped stack at a tiny size, for the CPU tests: the
chip benchmark's family (``model_config``, the program's layout) and its
plain float32 reference, loaded from ``benchmarks/chip``, over a
configuration file's keys.

Every mechanism of the published configuration is kept: Mamba-2 and NoPE
attention mixers in one stack, a MoE with a shared expert after each, a
router wider than the experts held, and the four scalars (embedding x12,
attention scale, residual x0.22, logits /16).
"""
import copy
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"

CONFIG = {
    "name": "granite-tiny", "source": "test",
    "family": "granite_hybrid", "reference": "granite_hybrid",
    "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 5, "vocab_size": 512,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 256,
    "hidden_act": "silu", "tie_word_embeddings": True,
    "torch_dtype": "float32", "compute_dtype": "float32",
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "position_embedding_type": "nope",
    "layer_types": ["attention", "mamba", "mamba", "attention", "mamba",
                    "mamba"],
    "num_experts_per_tok": 3, "num_local_experts": 2,
    "mamba_expand": 2, "mamba_d_head": 16, "mamba_n_heads": 8,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 8,
    # layers 1-5 of the six: m m A m m, a cut inside the period
    "deployment": {"layers_held": [1, 5], "router_experts": 8,
                   "experts_held": [2, 5]},
}


def _load(kind: str, name: str):
    for sub in ("", "metrics", "traffic"):
        if str(CHIP / sub) not in sys.path:
            sys.path.insert(0, str(CHIP / sub))
    mod_name = f"{kind}_{name}"
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            mod_name, CHIP / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[mod_name]


def family():
    return _load("families", "granite_hybrid")


def reference():
    return _load("references", "granite_hybrid")


def config(**updates) -> dict:
    c = copy.deepcopy(CONFIG)
    c.update(updates)
    return c


def model(conf=None):
    """``(ModelConfig, params)`` of a configuration (default the tiny
    one), weights from the family at seed 0."""
    conf = conf or CONFIG
    fam = family()
    return fam.model_config(conf), fam.make_params(conf, 0)


def bf16(params):
    """The weights rounded to bfloat16 and back (a lower precision than
    the configuration states)."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
