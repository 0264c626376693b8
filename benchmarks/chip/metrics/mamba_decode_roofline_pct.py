"""mamba_decode_roofline_pct: the least time the chip needs for the Mamba
decode kernel's needed work in the window's steps (each live slot's
float32 SSM state read and written once per Mamba layer, with the
kernel's inputs and output; the family's ``mamba_decode_work``), over the
summed device time of the ops named ``mamba_decode_step`` in the trace of
the window.  A step's live slots are the tokens it decoded.  Reads
nothing where the family counts no such work or the trace holds no such
op (a program without the kernel)."""
import flops
import stats

# the name the Pallas kernel carries in the TPU trace (its pallas_call
# and named scope)
KERNEL = "mamba_decode_step"


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
    work = getattr(run.fam, "mamba_decode_work", None)
    if work is None or run.peak is None:
        return None
    k = kernel_seconds(run)
    if not k:
        return None
    f, b = work(run.config, run.layers)
    need = sum(flops.roofline_seconds(s.emitted * f, s.emitted * b,
                                      run.peak)
               for s in stats.window_steps(run) if s.emitted)
    return 100.0 * need / k if need else None
