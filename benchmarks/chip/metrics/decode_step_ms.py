"""decode_step_ms: the engine's own decode-sweep time
(``engine.step_times``, host clock around the sweep and its device_get)
averaged over the window's steps that decoded."""
import stats


def read(run):
    steps = [s for s in stats.window_steps(run) if s.emitted]
    if not steps:
        return None
    return 1000.0 * sum(s.decode_s for s in steps) / len(steps)
