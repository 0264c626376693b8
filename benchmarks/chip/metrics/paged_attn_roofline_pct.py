"""paged_attn_roofline_pct: the least time the chip needs for the paged
decode attention's needed work in the window's steps (each slot's cached
rows read once, q and output, ``4 * heads * head_dim`` FLOPs per key;
``flops.paged_attn_work``), over the kernel's summed device time in the
trace of the window.

The kernel is matched by the name it has in the trace, read by hand on a
TPU v5e trace: the Pallas kernel sits under
``lax.platform_dependent``, so its op is ``%branch_0_fun.<n> = ...
custom-call(...), custom_call_target="tpu_custom_call"``; it is the only
Mosaic kernel the served path runs.  A stable kernel name is for a later
tracing change."""
import flops
import stats

# what the Pallas decode kernel's op text holds in the TPU trace
KERNEL = 'custom_call_target="tpu_custom_call"'


def kernel_seconds(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    total = 0.0
    for ops in run.trace.device_ops.values():
        total += sum(min(e, hi) - max(s, lo) for n, s, e in ops
                     if KERNEL in n and e > lo and s < hi)
    return total / 1e9 / max(1, len(run.trace.device_ops))


def read(run):
    k = kernel_seconds(run)
    if not k or run.peak is None:
        return None
    need = sum(flops.roofline_seconds(s.attn_flops, s.attn_bytes, run.peak)
               for s in stats.window_steps(run) if s.attn_bytes)
    return 100.0 * need / k if need else None

