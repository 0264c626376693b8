"""step_host_ms: host time per engine tick: each ``engine.tick`` span
that begins in the traced window, less the union of the ``engine.wait``
spans inside it (the host blocked on the device), averaged in ms."""
import program_spans
import xplane


def read(run):
    ticks = program_spans.spans(run, "engine.tick")
    if not ticks:
        return None
    waits = program_spans.spans(run, "engine.wait")
    host = sum((e - s) - xplane.busy_ns(waits, s, e) for _, s, e in ticks)
    return host / len(ticks) / 1e6
