"""step_mfu_pct: model FLOPs of the real (unpadded) prompt and output
tokens the window's steps produced (``flops.py``), over the steps' summed
wall time times the chip's peak bf16 rate (``peaks.json``)."""
import stats


def read(run):
    steps = stats.window_steps(run)
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or not wall or run.peak is None:
        return None
    return 100.0 * sum(s.flops for s in steps) / (
        wall * run.peak["bf16_flops_per_s"])
