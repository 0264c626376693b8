"""pool_used_pct: blocks in use (prefix-cache holds included) over the
pool's blocks, after each decode step of the window, averaged."""
import stats


def read(run):
    steps = [s for s in stats.window_steps(run) if s.emitted]
    if not steps:
        return None
    total = run.eng["slots"] * run.eng["max_seq"] // run.eng["block_size"]
    return 100.0 * sum(s.blocks_used for s in steps) / (len(steps) * total)
