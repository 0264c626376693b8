"""slots_busy_pct: decode tokens per step over the slots, over the
window's steps: how full the decode batch ran."""
import stats


def read(run):
    steps = stats.window_steps(run)
    if not steps:
        return None
    return 100.0 * sum(s.emitted for s in steps) / (
        len(steps) * run.eng["slots"])
