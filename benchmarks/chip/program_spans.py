"""The serving engine's own spans in a traced run.

The engine opens a profiler annotation (``engine.tick``, ``engine.admit``,
``engine.prefill``, ``engine.step``, ``engine.blocks``,
``engine.dispatch``, ``engine.wait``, ``engine.bookkeep``,
``engine.swap``) around each part of its work, so they lie in the run's
``.xplane.pb`` on the host's planes, on the same clock as the device's
ops.  They are read against a device plane: where the trace has none (a
rehearsal on the CPU, whose "device" is the host's own cores), host time
and time waiting on the device are not apart, and nothing is read.  A
program without the spans gives an empty list, and the metrics that read
them read nothing.
"""
from __future__ import annotations

from typing import List, Optional

import xplane

PREFIX = "engine."


def load(path) -> List[xplane.Interval]:
    """Every host event of the ``.xplane.pb`` at ``path`` whose name
    starts with ``engine.``, its name cut at ``#`` (TraceMe metadata)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = float(e.start_ns)
                    out.append((e.name.split("#", 1)[0], s,
                                s + float(e.duration_ns)))
    return out


def spans(run, name: Optional[str] = None
          ) -> Optional[List[xplane.Interval]]:
    """The engine spans that begin in the traced window, ends clipped to
    it (all of them, or those named ``name``); None when the run was not
    traced or its trace holds no device plane.  Read once and kept on the
    run."""
    if run.trace is None or not run.trace.device_ops:
        return None
    kept = getattr(run, "program_spans", None)
    if kept is None:
        lo, hi = run.trace_window
        kept = [(n, s, min(e, hi))
                for n, s, e in load(xplane.find_xplane(run._trace_dir))
                if lo <= s < hi]
        run.program_spans = kept
    return [sp for sp in kept if name is None or sp[0] == name]
