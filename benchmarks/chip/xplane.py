"""The one reduction from a profiler trace to the numbers the benchmark
reads: device-op intervals per chip, busy time as their union, time summed
by op name, and idle gaps named by what the host was doing.

A JAX profile is an ``.xplane.pb`` under ``<dir>/plugins/profile/<time>/``.
Each TPU chip is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per operation the chip ran, with a start and a duration in
nanoseconds on the trace's clock.  The host's planes hold the benchmark's
own ``bench.*`` annotations (``jax.profiler.TraceAnnotation``) on the same
clock.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_MARK = "bench."

Interval = Tuple[str, float, float]          # name, start_ns, end_ns


@dataclass
class Trace:
    """Device-op intervals per device plane, and the benchmark's host
    annotations, both in nanoseconds on the trace's clock."""
    device_ops: Dict[str, List[Interval]] = field(default_factory=dict)
    host_spans: List[Interval] = field(default_factory=list)


def find_xplane(logdir) -> Path:
    found = sorted(Path(logdir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path) -> Trace:
    """Read one ``.xplane.pb`` (or the newest under a trace directory)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    pd = ProfileData.from_file(str(path))
    out = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, float(e.start_ns),
                             float(e.start_ns) + float(e.duration_ns))
                            for e in line.events]
            out.device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.host_spans += [(e.name, float(e.start_ns),
                                    float(e.start_ns) + float(e.duration_ns))
                                   for e in line.events
                                   if e.name.startswith(HOST_MARK)]
    return out


def union(intervals: List[Interval], lo: float = float("-inf"),
          hi: float = float("inf")) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` of the intervals, clipped to ``[lo, hi]``."""
    spans = sorted((max(s, lo), min(e, hi)) for _, s, e in intervals
                   if e > lo and s < hi)
    merged: List[List[float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: List[Interval], lo: float = float("-inf"),
            hi: float = float("inf")) -> float:
    """Nanoseconds in which at least one op ran."""
    return sum(e - s for s, e in union(intervals, lo, hi))


def by_name(intervals: List[Interval]) -> Dict[str, float]:
    """Nanoseconds summed per op name (overlaps counted per op)."""
    out: Dict[str, float] = {}
    for name, s, e in intervals:
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def host_window(trace: Trace, name: str = "bench.window"):
    """``(start, end)`` of the benchmark's window annotation, or None."""
    spans = [(s, e) for n, s, e in trace.host_spans if n == name]
    return (min(s for s, _ in spans), max(e for _, e in spans)) \
        if spans else None


def idle_gaps(intervals: List[Interval], host: List[Interval], lo: float,
              hi: float, top: int = 10) -> List[Tuple[str, float]]:
    """The longest stretches in ``[lo, hi]`` with no op on the device, each
    named by the innermost benchmark annotation that covers its middle
    (``host-other`` where none does): ``[(name, seconds), ...]``."""
    busy = union(intervals, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    marks = [(n, s, e) for n, s, e in host if n != "bench.window"]
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(e2 - s2, n) for n, s2, e2 in marks if s2 <= mid <= e2]
        named.append((min(cover)[1] if cover else "host-other",
                      (e - s) / 1e9))
    named.sort(key=lambda g: -g[1])
    return named[:top]
