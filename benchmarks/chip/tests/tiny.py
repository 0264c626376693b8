"""Tiny cells for rehearsing the harness on the CPU."""
import copy
import json
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]

TINY_MODEL = {
    "name": "tiny-dense", "source": "test",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "max_position_embeddings": 256, "hidden_act": "silu",
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "compute_dtype": "bfloat16", "family": "dense_llama",
    "reference": "dense_llama", "correct": {"max_gap": 0.05},
}

# the real mix's shape at a tiny size: a closed loop caught mid-flight
CLOSED_MIX = {
    "loop": "closed", "clients": 4, "rounds": 200, "start": "stationary",
    "max_burst": 2,
    "engine": {"slots": 4, "max_seq": 128, "block_size": 16},
    "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
               "lo": 8, "hi": 30},
    "output": {"dist": "lognormal", "median": 10, "sigma": 0.5,
               "lo": 4, "hi": 20},
}


def bench():
    """``BENCHMARK.json`` as committed: the tiny cells below carry the
    name of its cell, so they report the same metrics."""
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell():
    """``(cell, config, mix)`` of the tiny closed-loop cell."""
    c = {"name": "yi4l-decode-long", "config": "tiny-dense",
         "traffic": "closed", "chips": 1, "why": "test"}
    return c, copy.deepcopy(TINY_MODEL), copy.deepcopy(CLOSED_MIX)


# a mid-width dense model in bf16, for the check's control: wide enough
# that the int8 control's error stands clear of bf16's
MID_MODEL = dict(TINY_MODEL, name="mid-dense", hidden_size=1024,
                 intermediate_size=2816, num_attention_heads=8,
                 num_key_value_heads=2, head_dim=128, num_hidden_layers=2,
                 vocab_size=8192, torch_dtype="bfloat16", rope_theta=5e6,
                 rms_norm_eps=1e-5)


def mid_cell():
    """A closed-loop cell of the mid model: 4 slots x 256, prompts 40–60,
    outputs 60–100, from a standing start.  Limit from CPU readings
    (8 seeds): program ``mean_gap`` 2e-5–4.2e-4, int8 control
    1.26e-3–3.43e-3."""
    mix = copy.deepcopy(CLOSED_MIX)
    mix.update(prompt={"dist": "uniform", "lo": 40, "hi": 60},
               output={"dist": "uniform", "lo": 60, "hi": 100},
               engine={"slots": 4, "max_seq": 256, "block_size": 16},
               max_burst=4)
    del mix["start"]
    config = dict(MID_MODEL, correct={"mean_gap": 9e-4})
    c = {"name": "yi4l-decode-long", "config": "mid-dense",
         "traffic": "closed", "chips": 1, "why": "test"}
    return c, config, mix
