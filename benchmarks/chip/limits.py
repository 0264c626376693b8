"""Readings for the output check's limits: the numbers ``run.py`` compares,
on many seeds in one process, for the program as it is and for the
control, which puts the reference with every matmul in int8 (weights per
output channel, activations per token) in the program's place, at each
position of the same prompts and tokens.

  python benchmarks/chip/limits.py --workload <name> --seeds 11,12,13 \
      --seconds 15 [--control]

Each seed makes its own weights and traffic and serves a short window at
the cell's own load; one JSON line per seed.  A tool for setting limits
(``PERF.md`` gives the readings and the limit), never part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, mix = harness.load_cell(bench, args.workload)
    harness.import_program()
    harness.enable_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = harness.Run(cell, config, mix, seed, args.seconds, False,
                          control=args.control)
        run.setup(time.perf_counter())
        run.window()
        run.free_engine()
        check = run.check()
        print("limits " + json.dumps({
            "workload": args.workload, "seed": seed,
            "control": args.control, "requests": run.check_requests,
            "tokens": run.check_tokens, "check_s": run.check_s,
            "setup_s": run.setup_s, "max_gap": float(run.gaps.max()),
            "mean_gap": float(run.gaps.mean()),
            "off_argmax": int((run.gaps > 0).sum()),
            **{k: v["value"] for k, v in check.items()}}), flush=True)
        del run
        gc.collect()


if __name__ == "__main__":
    main()
