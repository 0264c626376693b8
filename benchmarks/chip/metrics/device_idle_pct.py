"""device_idle_pct: 1 minus the union of the device's op intervals over
the traced window (``bench.window`` in the trace), averaged over chips."""


def read(run):
    if run.trace is None or not run.busy_s or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.trace_window_s)
