"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

From the root of a checkout on a machine with the TPU chips the cell asks
for.  ``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics (from a profiler trace of the window).  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``), and last ``check``, the numbers the output check compared,
each with its limit; the same numbers are the last lines on standard error.

Exits non-zero without that line when JAX finds no TPU, or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP / "metrics"))

import harness  # noqa: E402


def applicable(metrics, cell_name: str, e2e_names=None):
    """The metrics of ``metrics`` that this cell reports: those that list
    it under ``workloads``, or that list none and (per-layer) move an
    end-to-end metric the cell reports."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif e2e_names is None or m.get("moves") in e2e_names:
            out.append(m)
    return out


def read_metrics(run, metrics) -> dict:
    out = {}
    for m in metrics:
        v = importlib.import_module(m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def reduce_trace(run) -> dict:
    """Busy and window seconds of the traced window, and the breakdown."""
    import xplane
    run.finish_trace()
    tr = run.trace
    win = xplane.host_window(tr)
    if win is None:
        raise RuntimeError("the trace holds no bench.window annotation")
    run.trace_window = win
    run.trace_window_s = (win[1] - win[0]) / 1e9
    planes = list(tr.device_ops.values())
    if not planes:
        if run.require_tpu:
            raise RuntimeError("the trace holds no TPU device plane")
        run.busy_s = 0.0
        return {"device_ops": [], "idle_gaps": []}
    run.busy_s = sum(xplane.busy_ns(ops, *win) for ops in planes) \
        / 1e9 / len(planes)
    ops = [(n, max(s, win[0]), min(e, win[1])) for n, s, e in planes[0]
           if e > win[0] and s < win[1]]
    top = sorted(xplane.by_name(ops).items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, t / 1e9] for n, t in top],
            "idle_gaps": [list(g) for g in
                          xplane.idle_gaps(planes[0], tr.host_spans, *win)]}


def execute(args, require_tpu: bool = True, bench=None, loaded=None,
            out=print):
    """One run; returns the result dict (also printed as the last line).
    Tests pass ``bench`` and ``loaded`` (a cell, its configuration and its
    mix) and ``require_tpu=False`` to rehearse a run on the CPU."""
    bench = bench or harness.load_benchmark()
    cell, config, mix = loaded or harness.load_cell(bench, args.workload)
    harness.import_program()
    harness.enable_cache()
    run = harness.Run(cell, config, mix, args.seed, args.seconds,
                      bool(args.trace), require_tpu=require_tpu)
    run.setup(T_PROCESS if require_tpu else time.perf_counter())
    sc, sl, ss = run.setup_compiles
    out(f"setup: {run.setup_s:.3f} s; programs compiled {sc} "
        f"({ss:.2f} s), loaded from cache {sl}", file=sys.stderr)
    run.window()
    wc = run.compiles
    out(f"window: {run.seconds:.0f} s, compiles {wc.compiled}, cache loads "
        f"{wc.loaded} (both must be 0)", file=sys.stderr)
    mem = run.memory_peak()
    e2e_here = applicable(bench["end_to_end"], cell["name"])
    device = dict(run.device, memory_peak_bytes=mem)
    result_extra = {}
    if args.trace:
        result_extra["breakdown"] = reduce_trace(run)
        device["busy_s"] = run.busy_s
        device["window_s"] = run.trace_window_s
        metrics = read_metrics(run, applicable(
            bench["per_layer"], cell["name"], {m["name"] for m in e2e_here}))
    else:
        metrics = read_metrics(run, e2e_here)
    _summary(run, out)
    run.free_engine()
    check = run.check()
    result = {"correct": harness.correct_from(check),
              "attempted": run.submitted, "failed": run.budget_misses,
              "metrics": metrics, "device": device,
              **result_extra,
              "check": {k: {"value": v["value"], "limit": v["limit"],
                            "op": v["op"]} for k, v in check.items()}}
    out(f"check: {run.check_requests} requests, {run.check_tokens} served "
        f"tokens against the reference in {run.check_s:.2f} s; widest gap "
        f"{float(run.gaps.max()) if len(run.gaps) else 0.0}", file=sys.stderr)
    for k, v in check.items():
        out(f"{k} {v['value']} (limit {v['op']} {v['limit']})",
            file=sys.stderr)
    out(json.dumps(result))
    return result


def _summary(run, out) -> None:
    """Medians and counts, on a line of their own."""
    import stats
    gaps = stats.window_gaps(run)
    parts = [f"requests {len(run.tracks)}",
             f"window tokens {stats.window_tokens(run)}",
             f"steps {len(stats.window_steps(run))}",
             f"itl_p50_ms {1000 * (stats.pct(gaps, 50) or 0):.3f} "
             f"(n={len(gaps)})"]
    steps = stats.window_steps(run)
    parts.append(f"admitted {sum(1 for s in steps if s.prefill_tokens)} "
                 f"steps with a prefill")
    parts.append(f"longest step ms "
                 f"{1000 * max((s.t1 - s.t0 for s in steps), default=0):.1f}")
    out("summary: " + "; ".join(parts), file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        execute(args)
    except harness.NoChip as e:
        print(str(e.code), file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
