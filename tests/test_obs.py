"""Property suite for the observability layer: one timeline, no drift.

* **Span discipline** — every trace an engine emits is well-nested per
  ``(pid, tid)`` track and monotone on the wall clock, across both
  decode modes, random request mixes, and a mid-run ``swap_model``
  (which force-closes every in-flight slot span with a
  ``swap_requeue`` reason).  When ``hypothesis`` is installed the same
  property runs over generated mixes; otherwise a fixed-seed
  parametrization covers the same space.
* **Accounting** — one ``req.first_token`` instant per admission, one
  ``engine.prefill`` span per prefill jit call, and per-rid
  ``admissions + decode instants == len(generated)`` (so the trace and
  the token streams can never disagree about throughput).
* **TTFT bit-equality** — ``request_ttft_s`` equals the legacy
  ``first_token_s - arrived_s`` subtraction exactly, because the
  instants carry the very floats the engine stamps on the request.
* **Views, not copies** — ``ServeStats`` attributes and
  ``step_time_ewma_s`` read the metrics registry; :class:`EwmaGauge`
  reproduces the historical ``0.8*prev + 0.2*x`` fold bit-for-bit; P²
  histogram quantiles track ``np.percentile`` on a heavy-tailed stream.
* **Fleet timeline** — a placement-enabled fleet run with an
  engine-backed device and a mid-run ``drop_device`` produces events in
  all four layers, every one stamped on the simulated clock, monotone
  per track, exporting to a Chrome trace that ``tools/check_trace.py``
  accepts; report totals equal the records-derived sums.
* **Null path** — the default :data:`NULL_RECORDER` records nothing and
  token streams are bit-identical with tracing on and off.
"""
import importlib.util
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.monitor import ResourceContext
from repro.fleet import FleetController, build_fleet, fleet_report
from repro.models.configs import InputShape
from repro.models.model import init_params
from repro.obs import (LAYERS, NULL_RECORDER, EwmaGauge, Histogram,
                       MetricsRegistry, SLOClass, SLOTracker, TraceRecorder,
                       chrome_trace, instants, request_token_counts,
                       request_ttft_s, spans, write_trace)
from repro.serving import CompileCache, Request, ServingEngine

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

CFG = get_config("paper-backbone").with_updates(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=300)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
CC = CompileCache()          # shared: each program compiles exactly once

_ct_spec = importlib.util.spec_from_file_location(
    "check_trace",
    Path(__file__).resolve().parents[1] / "tools" / "check_trace.py")
check_trace = importlib.util.module_from_spec(_ct_spec)
_ct_spec.loader.exec_module(check_trace)


def _prompt(length, rid):
    rng = np.random.default_rng(101 * length + rid)
    return rng.integers(0, CFG.vocab_size, size=length).astype(np.int32)


def _run_engine(mix, mode, swap=False):
    """Run a request mix to completion under a TraceRecorder; optionally
    swap the model after the first step (re-queueing whatever is in
    flight).  Returns (recorder, engine, requests)."""
    rec = TraceRecorder()
    eng = ServingEngine(CFG, PARAMS, slots=2, max_seq=64,
                        decode_mode=mode, compile_cache=CC,
                        recorder=rec, pid="dev0")
    reqs = [Request(rid=i, prompt=_prompt(n, i), max_new_tokens=budget)
            for i, (n, budget) in enumerate(mix)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    if swap:
        eng.swap_model(CFG, PARAMS, eng.opts)
    eng.drain()
    return rec, eng, reqs


def _assert_trace_properties(rec, eng, reqs):
    # well-nested per track: spans() raises on any mismatched edge
    all_spans = spans(rec)
    # wall clock monotone within each (pid, tid) track
    last = {}
    for e in rec.events:
        key = (e.pid, e.tid)
        assert e.wall_s >= last.get(key, float("-inf")), \
            f"wall clock went backwards on {key} at {e.name}"
        last[key] = e.wall_s
    # standalone engine: no sim clock anywhere
    assert all(e.sim_s is None for e in rec.events)
    # accounting: admissions match first-token instants, every slot
    # occupancy is either a prefill admission or a thaw re-admission
    # (swap_model requeues in-flight requests via freeze/thaw, which
    # opens a fresh req.slot span without a new first token), prefill
    # spans match prefill jit calls, decodes complete the streams
    counts = request_token_counts(rec)
    admissions = sum(d["admissions"] for d in counts.values())
    decodes = sum(d["decodes"] for d in counts.values())
    assert admissions == eng.stats.prefills
    assert len(spans(rec, name="req.slot")) == admissions + eng.stats.thaws
    assert len(spans(rec, name="engine.prefill")) == eng.stats.prefill_calls
    assert admissions + decodes == eng.stats.tokens_out
    for r in reqs:
        # a swap freezes and re-queues the SAME object; its stream is
        # complete only once it finished (the aggregate tokens_out
        # check above covers anything still in flight)
        if not r.done or not r.generated:
            continue
        d = counts[r.rid]
        assert d["admissions"] + d["decodes"] == len(r.generated)
    # TTFT from spans == legacy subtraction, bit for bit
    span_ttft = request_ttft_s(rec)
    for r in reqs:
        if r.first_token_s is None:
            assert r.rid not in span_ttft
        else:
            assert span_ttft[r.rid] == r.first_token_s - r.arrived_s
    return all_spans


FIXED_MIXES = [
    [(8, 3), (24, 5)],
    [(1, 1)],
    [(40, 2), (3, 6), (17, 4)],
    [(12, 4), (12, 4), (12, 4)],         # same bucket: a burst
]


@pytest.mark.parametrize("mode", ["batched", "per_slot"])
@pytest.mark.parametrize("swap", [False, True])
@pytest.mark.parametrize("mix", FIXED_MIXES,
                         ids=[f"mix{i}" for i in range(len(FIXED_MIXES))])
def test_trace_properties_fixed(mode, swap, mix):
    rec, eng, reqs = _run_engine(mix, mode, swap=swap)
    _assert_trace_properties(rec, eng, reqs)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(mix=st.lists(st.tuples(st.integers(1, 40), st.integers(1, 6)),
                        min_size=1, max_size=5),
           mode=st.sampled_from(["batched", "per_slot"]),
           swap=st.booleans())
    def test_trace_properties_hypothesis(mix, mode, swap):
        rec, eng, reqs = _run_engine(mix, mode, swap=swap)
        _assert_trace_properties(rec, eng, reqs)


@pytest.mark.parametrize("mode", ["batched", "per_slot"])
def test_swap_requeues_thaw_without_second_admission(mode):
    # budget outlives the first step, so the swap freezes and re-queues
    # the request; swapping to the SAME variant thaws it back with zero
    # re-prefill — one first_token instant, one thaw, and a second slot
    # span, while the interrupted span closes with reason=swap_requeue
    rec, eng, reqs = _run_engine([(8, 6)], mode, swap=True)
    counts = request_token_counts(rec)
    assert counts[0]["admissions"] == 1
    assert eng.stats.thaws == 1
    reasons = [s.args.get("reason") for s in spans(rec, name="req.slot")]
    assert reasons.count("swap_requeue") == 1
    assert len(spans(rec, name="req.slot")) == 2


def test_stats_are_views_over_registry():
    rec, eng, _ = _run_engine([(8, 3)], "batched")
    m = eng.metrics
    assert eng.stats.steps == m.counter("engine.steps").value
    assert eng.stats.tokens_out == m.counter("engine.tokens_out").value
    assert eng.stats.prefills == m.counter("engine.prefills").value
    assert eng.step_time_ewma_s == m.ewma("engine.step_time_s").value
    assert eng.stats.backend_compiles == \
        m.counter("engine.backend_compiles").value
    assert len(eng.step_times) == eng.stats.steps


def test_ewma_gauge_bit_identical_to_legacy_fold():
    rng = np.random.default_rng(0)
    xs = rng.uniform(1e-4, 5e-2, size=200).tolist()
    g = EwmaGauge("t", alpha=0.2)
    legacy = None
    for x in xs:
        got = g.update(x)
        legacy = x if legacy is None else 0.8 * legacy + 0.2 * x
        assert got == legacy          # exact: same float ops, same order


def test_p2_histogram_tracks_numpy_percentiles():
    rng = np.random.default_rng(7)
    xs = rng.lognormal(mean=-6.0, sigma=0.8, size=4000)
    h = Histogram("t", quantiles=(0.5, 0.95, 0.99))
    for x in xs:
        h.observe(float(x))
    assert h.count == len(xs)
    assert h.min == xs.min() and h.max == xs.max()
    for q in (0.5, 0.95):
        exact = float(np.percentile(xs, q * 100))
        assert abs(h.quantile(q) - exact) / exact < 0.15
    # exact below five samples (nearest-rank fallback)
    small = Histogram("s", quantiles=(0.5,))
    for x in (3.0, 1.0, 2.0):
        small.observe(x)
    assert small.quantile(0.5) == 2.0


def test_registry_name_means_one_thing():
    m = MetricsRegistry()
    c = m.counter("a.b")
    assert m.counter("a.b") is c
    with pytest.raises(TypeError):
        m.gauge("a.b")
    m.ewma("a.e").update(1.0)
    assert set(m.names()) == {"a.b", "a.e"}
    snap = m.snapshot()
    assert snap["a.b"] == 0 and snap["a.e"] == 1.0


def test_null_recorder_default_and_stream_equality():
    def streams(recorder):
        eng = ServingEngine(CFG, PARAMS, slots=2, max_seq=64,
                            compile_cache=CC, recorder=recorder)
        reqs = [Request(rid=i, prompt=_prompt(9 + i, i), max_new_tokens=5)
                for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.drain()
        return [tuple(r.generated) for r in reqs]

    default_eng = ServingEngine(CFG, PARAMS, slots=2, max_seq=64,
                                compile_cache=CC)
    assert default_eng.recorder is NULL_RECORDER
    rec = TraceRecorder()
    assert streams(NULL_RECORDER) == streams(rec)
    assert len(rec.events) > 0


def test_exporter_closes_dangling_spans_and_picks_wall_clock():
    rec = TraceRecorder()
    rec.begin("outer", pid="p", tid="t", cat="engine", wall_s=1.0)
    rec.instant("tick", pid="p", tid="t", cat="engine", wall_s=2.0)
    doc = chrome_trace(rec)
    assert doc["otherData"]["clock"] == "wall"     # no sim clock anywhere
    ends = [e for e in doc["traceEvents"] if e["ph"] == "E"]
    assert len(ends) == 1 and ends[0]["args"]["open_at_export"]
    # synthetic end lands at the track's LAST ts, keeping it monotone
    assert ends[0]["ts"] == 2.0 * 1e6


def _fleet_run(tmp_path):
    cfg = CFG
    shape = InputShape("obs_t", 128, 2, "decode")
    fleet = build_fleet(5, seed=0)
    rec = TraceRecorder()
    ctl = FleetController(fleet, cfg, shape, trace_ticks=400,
                          warmup_ticks=2, placement=True, recorder=rec)
    engine_dev = next(d for d in fleet if d.tier == "light")
    eng = ctl.build_engine(engine_dev.device_id, PARAMS, cfg=cfg,
                           slots=2, max_seq=64, steps_per_tick=2)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=_prompt(6 + i, i),
                           max_new_tokens=8))
    ctl.run_for(4.0)
    dropped = next(d.device_id for d in fleet
                   if d.device_id != engine_dev.device_id)
    ctl.drop_device(dropped)
    ctl.run_for(4.0)
    eng.drain()                       # close in-flight request spans
    return rec, ctl, dropped


def test_fleet_trace_all_layers_one_sim_timebase(tmp_path):
    rec, ctl, dropped = _fleet_run(tmp_path)
    # every layer present, every event on the simulated clock
    cats = {e.cat for e in rec.events}
    assert cats == set(LAYERS)
    assert all(e.sim_s is not None for e in rec.events)
    # sim clock monotone per (pid, tid) track, spans well-nested
    last = {}
    for e in rec.events:
        key = (e.pid, e.tid)
        assert e.sim_s >= last.get(key, float("-inf"))
        last[key] = e.sim_s
    spans(rec)
    assert instants(rec, name="fleet.drop_device")
    assert spans(rec, name="placement.sweep")
    # the exported trace validates under the CI checker, all layers on
    doc = chrome_trace(rec)
    assert doc["otherData"]["clock"] == "sim"
    path = tmp_path / "fleet_trace.json"
    write_trace(rec, str(path))
    assert check_trace.check(path, require_layers=LAYERS) == 0
    # report totals are registry views that match the raw records
    rep = fleet_report(ctl)
    assert rep.total_violations == sum(1 for r in ctl.records if r.violated)
    assert rep.total_energy_j == pytest.approx(
        sum(r.observed_energy_j for r in ctl.records))
    assert ctl.wakes == len(ctl.records)
    # the placer left an audit trail and each decision also landed in
    # the trace as a placement.decide instant
    assert len(ctl.placer.audits) == len(
        instants(rec, name="placement.decide"))


# ------------------------------------------------------ exporter edges ----
def test_exporter_auto_clock_mixed_events_and_sim_raise():
    rec = TraceRecorder()
    rec.instant("a", pid="p", tid="t", cat="engine", wall_s=1.0)
    rec.sim_clock = lambda: 5.0          # later events carry a sim stamp
    rec.instant("b", pid="p", tid="t", cat="engine", wall_s=2.0)
    # mixed sim/wall: "auto" must fall back to the wall clock (one
    # timeline, one timebase — never a mix)
    doc = chrome_trace(rec)
    assert doc["otherData"]["clock"] == "wall"
    with pytest.raises(ValueError):
        chrome_trace(rec, clock="sim")
    # the event that does carry a sim stamp preserves it in args
    rows = [r for r in doc["traceEvents"] if r["ph"] == "i"]
    assert rows[1]["args"]["sim_s"] == 5.0
    assert "args" not in rows[0]


def test_open_at_export_and_orphan_ends_roundtrip_check_trace(tmp_path):
    rec = TraceRecorder()
    rec.begin("outer", pid="p", tid="t", cat="engine", wall_s=1.0)
    rec.begin("inner", pid="p", tid="t", cat="engine", wall_s=2.0)
    rec.instant("tick", pid="p", tid="t", cat="engine", wall_s=3.0)
    path = tmp_path / "dangling.json"
    write_trace(rec, str(path))
    assert check_trace.check(path) == 0      # synthetic ends validate
    doc = json.loads(path.read_text())
    ends = [e for e in doc["traceEvents"] if e["ph"] == "E"]
    assert len(ends) == 2
    assert all(e["args"]["open_at_export"] for e in ends)
    # inner closes before outer (reverse stack order), both at last ts
    assert [e["name"] for e in ends] == ["inner", "outer"]
    # an END whose BEGIN never existed is skipped and counted, so even
    # that malformed recorder exports a validating document
    rec2 = TraceRecorder()
    rec2.end("ghost", pid="p", tid="t", cat="engine", wall_s=1.0)
    rec2.instant("tick", pid="p", tid="t", cat="engine", wall_s=2.0)
    doc2 = chrome_trace(rec2)
    assert doc2["otherData"]["orphaned_ends"] == 1
    assert not [e for e in doc2["traceEvents"] if e["ph"] == "E"]
    path2 = tmp_path / "orphan.json"
    path2.write_text(json.dumps(doc2))
    assert check_trace.check(path2) == 0


# -------------------------------------------------------- slo feedback ----
SHAPE = InputShape("obs_t", 128, 2, "decode")


def _slo_fleet(slo, cc, *, backlog_s=None, n_req=4, budget=6):
    """A placement-free fleet with one engine-backed light device.  With
    ``backlog_s`` the submitted requests claim to have arrived that far
    in the past — a deterministic load spike: their TTFTs are at least
    ``backlog_s`` regardless of machine speed."""
    fleet = build_fleet(5, seed=0)
    rec = TraceRecorder()
    ctl = FleetController(fleet, CFG, SHAPE, trace_ticks=400,
                          warmup_ticks=2, recorder=rec, compile_cache=cc,
                          slo=slo)
    dev = next(d for d in fleet if d.tier == "light")
    eng = ctl.build_engine(dev.device_id, PARAMS, cfg=CFG, slots=2,
                           max_seq=64, steps_per_tick=2)
    reqs = [Request(rid=i, prompt=_prompt(6 + i, i), max_new_tokens=budget)
            for i in range(n_req)]
    if backlog_s is not None:
        now = time.perf_counter()
        for r in reqs:
            r.arrived_s = now - backlog_s
    for r in reqs:
        eng.submit(r)
    ctl.run_for(4.0)
    eng.drain()
    return [tuple(r.generated) for r in reqs], eng, ctl, rec, dev.device_id


def test_slo_spike_pages_and_downshifts_within_two_wakes():
    # TTFT target 1s against a 10s backlog: the very first window burns
    # at 1/(1-0.95) = 20x, far past the page threshold (min_count=2:
    # the two engine slots admit two backlogged requests on the first
    # wake, which is all the evidence this spike needs)
    slo = SLOTracker(SLOClass(name="interactive", ttft_p95_s=1.0),
                     window_s=30.0, min_count=2)
    _, eng, ctl, rec, pid = _slo_fleet(slo, CompileCache(), backlog_s=10.0)
    assert eng.slo is slo                 # controller shared its tracker
    pages = instants(rec, name="slo.page")
    assert len(pages) == 1 and pages[0].args["burn"] > 1.0
    assert slo.pressure > 1.0             # long window: never released
    assert ctl.metrics.counter("fleet.slo_pressure_events").value == 1
    t_page = pages[0].sim_s
    # every device's FIRST decision after the page is the latency-first
    # downshift — pressure propagated within one wake of paging
    decides = instants(rec, name="loop.decide")
    after = {}
    for e in decides:
        if e.sim_s > t_page:
            after.setdefault(e.pid, e)
    assert after, "no fleet wakes after the page"
    for pid_, first in after.items():
        assert first.args["reason"] == "slo_pressure", \
            f"{pid_} first post-page decision was {first.args['reason']}"
        assert first.args["pressure"] > 1.0
    # the downshift is real: under a nominal context the pressure-picked
    # action is no slower than the device's last healthy choice
    loop = ctl._devices[pid].loop
    healthy = [d for d in loop.decisions if d.reason != "slo_pressure"]
    pressed = [d for d in loop.decisions if d.reason == "slo_pressure"]
    assert healthy and pressed
    nominal = ResourceContext()

    def raw_latency(d):
        return loop.evaluator.evaluate(d.action, nominal,
                                       calibrate=False).latency_s

    assert raw_latency(pressed[-1]) <= raw_latency(healthy[-1])
    # the burn window and page both landed on the fault/SLO report
    from repro.faults import summarize_faults
    summ = summarize_faults(rec.events)
    assert summ["slo_pages"] == 1


def test_slo_healthy_run_bit_identical_to_untracked_and_no_recompiles():
    cc = CompileCache()
    warm, _, _, _, _ = _slo_fleet(None, cc)          # compile everything
    base, base_eng, _, base_rec, _ = _slo_fleet(None, cc)
    assert base == warm
    slo = SLOTracker(SLOClass(ttft_p95_s=1e3, tpot_p95_s=1e3))
    got, eng, ctl, rec, pid = _slo_fleet(slo, cc)
    # bit-identical token streams, and the warm cache stayed warm: the
    # feedback path compiled nothing and decided nothing differently
    assert got == base
    assert eng.stats.recompiles == 0 and base_eng.stats.recompiles == 0
    assert slo.pressure == 0.0
    assert not instants(rec, name="slo.page")
    assert not instants(rec, name="slo.burn")
    assert ctl.metrics.counter("fleet.slo_pressure_events").value == 0
    assert not any(d.reason == "slo_pressure"
                   for dd in ctl._devices.values()
                   for d in dd.loop.decisions)
    # the tracker did observe the healthy traffic (it wasn't bypassed);
    # the 4s horizon rotated several 1s windows, so count across the
    # closed-window history plus the live window
    ttft = sum(w["counts"]["ttft"] for w in slo.history)
    tpot = sum(w["counts"]["tpot"] for w in slo.history)
    if slo._live is not None:
        ttft += slo._live.counts["ttft"]
        tpot += slo._live.counts["tpot"]
    assert ttft >= 2 and tpot > 0
    assert all(w["burn"] == 0.0 for w in slo.history)
    # tracker state serializes with full histogram marker state
    state = slo.state()
    assert state["pressure"] == 0.0
    json.dumps(state)                      # fully serializable
