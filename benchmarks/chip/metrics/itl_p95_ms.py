"""itl_p95_ms: 95th percentile of every gap between consecutive tokens of
a request, both stamped inside the window, over all requests."""
import stats


def read(run):
    v = stats.pct(stats.window_gaps(run), 95)
    return None if v is None else 1000.0 * v
