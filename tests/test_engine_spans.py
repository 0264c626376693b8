"""The serving engine's spans on the profiler's clock, its stable program
names, and its backend-compile counter.

* **Spans** — a paged engine's ticks, recorded by ``jax.profiler`` on
  the CPU, hold every ``engine.*`` span, nested as the engine opens
  them, with one ``engine.tick`` per step and one ``engine.prefill`` per
  prefill call.
* **No cost to the streams** — the default :data:`NULL_RECORDER` and a
  :class:`TraceRecorder` serve bit-identical tokens with the same
  program-cache misses, and a span builds its metadata only where a
  sink records.
* **Names** — the decode step's module is ``jit_paged_decode``.
* **Backend compiles** — counted inside ticks: a cold engine's first
  step compiles, a second engine over the same programs does not.
"""
import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models.model import init_params
from repro.obs import NULL_RECORDER, TraceRecorder, profiling, spans
from repro.serving import CompileCache, Request, ServingEngine

CFG = get_config("paper-backbone").with_updates(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=300)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
CC = CompileCache()

# the span each engine span opens inside (engine.wait: wherever the host
# blocks on the device)
PARENTS = {"engine.tick": {None}, "engine.swap": {None},
           "engine.admit": {"engine.tick"},
           "engine.prefill": {"engine.admit"},
           "engine.step": {"engine.tick"},
           "engine.blocks": {"engine.step"},
           "engine.dispatch": {"engine.step"},
           "engine.bookkeep": {"engine.step"},
           "engine.wait": {"engine.step", "engine.prefill", "engine.admit",
                           "engine.blocks", "engine.swap"}}


def _engine(recorder=NULL_RECORDER, cc=CC):
    return ServingEngine(CFG, PARAMS, slots=2, max_seq=64,
                         decode_mode="paged", compile_cache=cc,
                         recorder=recorder, pid="dev0")


def _requests():
    rng = np.random.default_rng(7)
    return [Request(rid=i, prompt=rng.integers(1, CFG.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=budget)
            for i, (n, budget) in enumerate([(12, 6), (20, 9), (5, 4),
                                             (12, 5)])]


def _serve(eng, swap_after=None):
    reqs = _requests()
    for r in reqs:
        eng.submit(r)
    n = 0
    while eng.has_work:
        eng.step()
        n += 1
        if n == swap_after:
            eng.swap_model(CFG, PARAMS, eng.opts)
    return reqs


def _engine_events(logdir):
    """``(name, start_ns, end_ns, stats)`` of every host ``engine.*``
    event in the trace under ``logdir``."""
    path, = logdir.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("engine.")]
    return out


def _parent(ev, events):
    """The innermost other event that holds ``ev``, by name."""
    name, s, e, _ = ev
    holders = [(e2 - s2, n2) for n2, s2, e2, _ in (x for x in events
                                                  if x is not ev)
               if s2 <= s and e <= e2]
    return min(holders)[1] if holders else None


def test_spans_on_the_profiler_clock(tmp_path):
    eng = _engine()
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng, swap_after=2)
    events = _engine_events(tmp_path)
    names = [n for n, *_ in events]
    assert set(names) == set(PARENTS)
    for ev in events:
        assert _parent(ev, events) in PARENTS[ev[0]], ev[:3]
    assert names.count("engine.tick") == eng.stats.steps
    assert names.count("engine.prefill") == eng.stats.prefill_calls
    # metadata rides on the profiler's span while it records
    prefill = next(ev for ev in events if ev[0] == "engine.prefill")
    assert prefill[3]["bucket"] == 16
    swap = next(ev for ev in events if ev[0] == "engine.swap")
    assert swap[3]["generation"] == 1


def test_null_recorder_serves_the_trace_recorders_streams():
    assert not profiling()
    null_eng = _engine(cc=CompileCache())
    rec = TraceRecorder()
    traced_eng = _engine(rec, cc=CompileCache())
    null = _serve(null_eng, swap_after=3)
    traced = _serve(traced_eng, swap_after=3)
    assert [r.generated for r in null] == [r.generated for r in traced]
    assert null_eng.stats.recompiles == traced_eng.stats.recompiles
    assert null_eng.stats.thaws == traced_eng.stats.thaws > 0
    # the recorder holds the spans, well nested per track
    ticks = spans(rec, name="engine.tick")
    assert len(ticks) == traced_eng.stats.steps
    assert len(spans(rec, name="engine.swap")) == 1


def test_span_builds_metadata_only_where_a_sink_records():
    built = []

    def args():
        built.append(1)
        return {"k": 1}

    assert not profiling()
    with NULL_RECORDER.span("engine.x", pid="p", tid="engine", args=args):
        pass
    assert built == []
    rec = TraceRecorder()
    with rec.span("engine.x", pid="p", tid="engine", args=args):
        pass
    assert built == [1]
    assert [(e.name, e.ph, e.args) for e in rec.events] == [
        ("engine.x", "B", {"k": 1}), ("engine.x", "E", None)]


def test_span_closes_on_an_exception():
    rec = TraceRecorder()
    with pytest.raises(ValueError):
        with rec.span("engine.x", pid="p", tid="engine"):
            raise ValueError
    assert [e.ph for e in rec.events] == ["B", "E"]


def test_decode_module_is_named():
    eng = _engine()
    assert "jit_paged_decode" in eng.lower_decode().as_text()


def test_backend_compiles_counted_inside_ticks():
    cc = CompileCache()
    cold = _engine(cc=cc)
    for r in _requests():
        cold.submit(r)
    cold.step()
    assert cold.stats.backend_compiles >= 1
    cold.drain()
    warm = _engine(cc=cc)
    _serve(warm)
    assert warm.stats.steps > 3
    assert warm.stats.backend_compiles == 0
