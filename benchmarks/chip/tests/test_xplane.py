"""The trace reduction: on hand-made intervals, and on a small trace that
a TPU v5e recorded (``data/``: a traced run of ``paper-steady-open``, cut
to the window's annotation and the middle 0.25 s of its ops and steps)."""
from pathlib import Path

import xplane

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "paper-steady-open.v5e.xplane.pb"

OPS = [("a", 0.0, 10.0), ("b", 5.0, 15.0), ("a", 20.0, 30.0),
       ("c", 40.0, 45.0)]
HOST = [("bench.window", 0.0, 50.0), ("bench.step", 0.0, 31.0),
        ("bench.wait", 31.0, 39.0), ("bench.adapt", 44.0, 50.0)]


def test_union_merges_overlaps_and_clips():
    assert xplane.union(OPS) == [(0.0, 15.0), (20.0, 30.0), (40.0, 45.0)]
    assert xplane.union(OPS, 8.0, 42.0) == [(8.0, 15.0), (20.0, 30.0),
                                            (40.0, 42.0)]


def test_busy_and_by_name():
    assert xplane.busy_ns(OPS) == 30.0
    assert xplane.busy_ns(OPS, 0.0, 50.0) == 30.0
    assert xplane.by_name(OPS) == {"a": 20.0, "b": 10.0, "c": 5.0}


def test_idle_gaps_named_by_the_host():
    gaps = xplane.idle_gaps(OPS, HOST, 0.0, 50.0)
    # 15-20 under bench.step, 30-40 mid 35 under bench.wait, 45-50 under
    # bench.adapt; longest first, in seconds
    assert gaps == [("bench.wait", 10e-9), ("bench.step", 5e-9),
                    ("bench.adapt", 5e-9)]


def test_host_window():
    t = xplane.Trace(device_ops={"/device:TPU:0": OPS}, host_spans=HOST)
    assert xplane.host_window(t) == (0.0, 50.0)
    assert xplane.host_window(xplane.Trace()) is None


def test_recorded_v5e_trace():
    import paged_attn_roofline_pct
    t = xplane.load(RECORDED)
    assert list(t.device_ops) == ["/device:TPU:0"]
    ops = t.device_ops["/device:TPU:0"]
    win = xplane.host_window(t)
    assert win is not None and 1.9e9 < win[1] - win[0] < 2.1e9
    steps = [(s, e) for n, s, e in t.host_spans if n == "bench.step"]
    assert len(steps) >= 8
    # the ops kept lie in the window, and the device is busy for part of
    # the stretch they cover
    lo, hi = min(s for _, s, _ in ops), max(e for *_, e in ops)
    assert win[0] <= lo and hi <= win[1]
    busy = xplane.busy_ns(ops, lo, hi)
    assert 0.3 * (hi - lo) < busy < hi - lo
    names = xplane.by_name(ops)
    kernel = [n for n in names if paged_attn_roofline_pct.KERNEL in n]
    assert kernel and all(n.startswith("%branch_0_fun") for n in kernel)
    gaps = xplane.idle_gaps(ops, t.host_spans, lo, hi)
    assert gaps and all(name.startswith("bench.") or name == "host-other"
                        for name, _ in gaps)
