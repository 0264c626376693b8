"""The engine's spans and the two metrics that read them: on hand-made
spans, and on a trace the profiler records here."""
from types import SimpleNamespace

import jax
import pytest

import prefill_ms
import program_spans
import step_host_ms

# two ticks in a window of 0-100 ns: the first waits 10-20 and 15-30 (a
# union of 20) and carries a prefill of 8; the second waits 60-65; a tick
# and a prefill that begin after the window are not read
SPANS = [("engine.tick", 0.0, 40.0), ("engine.admit", 1.0, 12.0),
         ("engine.prefill", 2.0, 10.0), ("engine.wait", 10.0, 20.0),
         ("engine.wait", 15.0, 30.0), ("engine.tick", 50.0, 70.0),
         ("engine.wait", 60.0, 65.0), ("engine.tick", 100.0, 140.0),
         ("engine.prefill", 101.0, 130.0)]


# a trace with a device plane, whose ops the spans are not read from
ON_CHIP = SimpleNamespace(device_ops={"/device:TPU:0": []})


def _run(spans, traced=True):
    run = SimpleNamespace(trace=ON_CHIP if traced else None,
                          trace_window=(0.0, 100.0), _trace_dir="unused")
    if traced:
        run.program_spans = [sp for sp in spans if sp[1] < 100.0]
    return run


def test_spans_by_name():
    run = _run(SPANS)
    assert len(program_spans.spans(run)) == 7
    assert program_spans.spans(run, "engine.tick") == [
        ("engine.tick", 0.0, 40.0), ("engine.tick", 50.0, 70.0)]


def test_step_host_ms_takes_out_the_waits():
    # (40 - 20) and (20 - 5), over 2 ticks, in ms
    assert step_host_ms.read(_run(SPANS)) == pytest.approx(17.5e-6)


def test_prefill_ms_is_the_mean_prefill():
    assert prefill_ms.read(_run(SPANS)) == pytest.approx(8e-6)


def test_nothing_to_read():
    for metric in (step_host_ms, prefill_ms):
        assert metric.read(_run(SPANS, traced=False)) is None
        assert metric.read(_run([])) is None          # a program without
        assert metric.read(_run([("bench.step", 0.0, 5.0)])) is None
        no_device = _run(SPANS)                       # a CPU rehearsal
        no_device.trace = SimpleNamespace(device_ops={})
        assert metric.read(no_device) is None


def test_spans_read_from_a_recorded_trace(tmp_path, monkeypatch):
    """Spans the profiler records, with metadata, read back by name,
    clipped to the window and kept on the run.  The CPU's trace has no
    device plane: nothing is read until one stands in for the chip's."""
    import xplane
    Annotation = jax.profiler.TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with Annotation("bench.window"):
            with Annotation("engine.tick"):
                with Annotation("engine.prefill", bucket=16, rids="[1, 2]"):
                    pass
                with Annotation("engine.wait"):
                    jax.device_get(jax.numpy.ones(4) * 2)
        with Annotation("engine.tick"):      # after the window
            pass
    trace = xplane.load(tmp_path)
    run = SimpleNamespace(trace=trace, trace_window=xplane.host_window(trace),
                          _trace_dir=tmp_path)
    assert program_spans.spans(run) is None
    trace.device_ops["/device:TPU:0"] = []
    names = [n for n, _, _ in program_spans.spans(run)]
    assert sorted(names) == ["engine.prefill", "engine.tick", "engine.wait"]
    (_, ts, te), = program_spans.spans(run, "engine.tick")
    (_, ws, we), = program_spans.spans(run, "engine.wait")
    assert ts <= ws < we <= te
    # read once: a second read takes what the run kept
    monkeypatch.setattr(program_spans, "load", None)
    assert len(program_spans.spans(run)) == 3
    assert step_host_ms.read(run) == pytest.approx(
        (te - ts - (we - ws)) / 1e6)
