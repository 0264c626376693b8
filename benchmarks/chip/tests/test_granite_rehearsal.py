"""The Granite-4.0-H family through ``run.execute`` on the CPU at a tiny
size: the harness as a benchmark run drives it, on three seeds and both trace
modes, each from an empty compile cache directory (the conftest's).

A run whose window compiles a program, or whose gap list comes out empty,
loses ``itl_p95_ms`` or fails here, not on the chip: every window line
must read ``compiles 0, cache loads 0``, and an untraced run must report
exactly the cell's three end-to-end metrics.
"""
import argparse
import copy

import pytest

import run
import tiny

# the family's keys at a tiny size: Mamba-2 and NoPE attention layers in
# one stack, a MoE with a shared expert after each, a router wider than
# the experts held, and the four Granite scalars
TINY_GRANITE = {
    "name": "tiny-granite", "source": "test",
    "family": "granite_hybrid", "reference": "granite_hybrid",
    "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 4, "vocab_size": 256,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 256,
    "hidden_act": "silu", "tie_word_embeddings": True,
    "torch_dtype": "float32", "compute_dtype": "bfloat16",
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "position_embedding_type": "nope",
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "num_experts_per_tok": 3, "num_local_experts": 3,
    "mamba_expand": 2, "mamba_d_head": 16, "mamba_n_heads": 8,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 16,
    "deployment": {"layers_held": [0, 3], "router_experts": 8,
                   "experts_held": [0, 1, 2]},
    # CPU readings (bf16 activations, f32 weights), 6 seeds: mean_gap
    # 1e-8 to 8e-5
    "correct": {"mean_gap": 2e-3},
}

CELL = {"name": "granite10l-sharegpt-128", "config": "tiny-granite",
        "traffic": "closed", "chips": 1, "why": "test"}
E2E = {"setup_s", "itl_p95_ms", "out_tok_s"}
# the CPU has no peak in peaks.json and no device plane in its trace, so
# step_mfu_pct, mamba_decode_roofline_pct, device_idle_pct, step_host_ms
# and prefill_ms find nothing to read
TRACED = {"slots_busy_pct", "pool_used_pct", "decode_step_ms"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("seed", [2 ** 33 + 101, 7, 2 ** 31 + 5])
def test_granite_cell_rehearsal(seed, trace):
    lines = []
    args = argparse.Namespace(workload=None, seed=seed, seconds=2.0,
                              trace=trace)
    result = run.execute(
        args, require_tpu=False, bench=tiny.bench(),
        loaded=(dict(CELL), copy.deepcopy(TINY_GRANITE),
                copy.deepcopy(tiny.CLOSED_MIX)),
        out=lambda *a, **k: lines.append(" ".join(a)))
    assert result["correct"] is True, result["check"]
    window = [ln for ln in lines if ln.startswith("window:")]
    assert len(window) == 1 and "compiles 0, cache loads 0" in window[0]
    assert set(result["metrics"]) == (TRACED if trace else E2E)
    assert result["attempted"] >= 4 and result["failed"] == 0
