"""``run.py`` end to end on the CPU at tiny sizes (the device check left
out), and the real command refusing a CPU."""
import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import run
import tiny

ROOT = Path(__file__).resolve().parents[3]


def _execute(trace=0, seconds=2.0, seed=2 ** 33 + 7):
    lines = []
    args = argparse.Namespace(workload=None, seed=seed, seconds=seconds,
                              trace=trace)
    result = run.execute(args, require_tpu=False, bench=tiny.bench(),
                         loaded=tiny.cell(),
                         out=lambda *a, **k: lines.append(" ".join(a)))
    return result, lines


def test_closed_loop_run():
    result, lines = _execute()
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "itl_p95_ms", "out_tok_s"}
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert any("compiles 0, cache loads 0" in ln for ln in lines)
    last = json.loads(lines[-1])
    assert list(last)[-1] == "check" and last["correct"] is True
    assert lines[-2].startswith("budget_misses")


def test_window_opens_on_a_full_engine():
    """Set-up admits every client's first request (mid-flight ones with
    the output they already produced in the prompt), so the window's
    first step decodes every slot."""
    result, _ = _execute(trace=1)
    assert result["correct"] is True
    m = result["metrics"]
    # the CPU has no peak in peaks.json and no device plane in its trace,
    # so step_mfu_pct, paged_attn_roofline_pct and device_idle_pct find
    # nothing to read and are left out
    assert set(m) == {"slots_busy_pct", "pool_used_pct", "decode_step_ms"}
    assert m["slots_busy_pct"]["value"] > 75
    assert result["device"]["window_s"] > 1.5
    assert "breakdown" in result


def test_real_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "yi4l-decode-long", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
