"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).

  PYTHONPATH=src python -m benchmarks.run [--only overall,engine,...]

``--summary`` additionally folds the resulting ``BENCH_*.json``
artifacts into one labelled row of ``BENCH_trajectory.json`` after the
suites run (``--summary-only`` skips the suites and just re-folds the
artifacts already on disk); the extraction and upsert live in
``tools/check_perf.py`` so the trajectory row and the regression gate
read the artifacts identically.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
import traceback
from pathlib import Path

SUITES = ("overall", "dynamic_budgets", "elastic", "offload", "engine",
          "ablation", "case_study", "tta", "roofline", "fleet", "serving",
          "placement", "faults", "paging")

ROOT = Path(__file__).resolve().parents[1]


def _check_perf():
    """Load tools/check_perf.py (tools/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        "check_perf", ROOT / "tools" / "check_perf.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def write_summary(label: str, root: Path = ROOT) -> None:
    cp = _check_perf()
    entry = cp.trajectory_entry(root, label)
    cp.append_trajectory(root / cp.TRAJECTORY, entry)
    print(f"trajectory,{label},"
          f"{json.dumps(entry, sort_keys=True, default=str)}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--summary", action="store_true",
                    help="append a BENCH_trajectory.json row after the "
                         "suites run")
    ap.add_argument("--summary-only", action="store_true",
                    help="skip the suites; fold the BENCH_*.json already "
                         "on disk into BENCH_trajectory.json")
    ap.add_argument("--label", default="head",
                    help="trajectory row label (rows are upserted by "
                         "label, e.g. pr9)")
    args = ap.parse_args()
    if args.summary_only:
        write_summary(args.label)
        sys.exit(0)
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    chosen = [s.strip() for s in args.only.split(",") if s.strip()] or SUITES
    print("name,us_per_call,derived")
    failures = 0
    for name in chosen:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run()
        except Exception:
            failures += 1
            print(f"bench_{name},0.0,ERROR")
            traceback.print_exc()
        print(f"bench_{name}.wall,{(time.time()-t0)*1e6:.0f},", flush=True)
    if args.summary and not failures:
        write_summary(args.label)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
