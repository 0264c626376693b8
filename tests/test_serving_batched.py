"""Slot-batched decode path: greedy streams must be bit-identical to the
per-slot reference under mixed prompt lengths, mid-stream admissions,
slot recycling and mid-decode variant swaps; the donated stacked cache
must never be reused; engines sharing a CompileCache must not recompile
shared programs (even with heterogeneous per-slot sampling and
mixed-size admission bursts); a burst of k same-bucket requests costs
exactly ONE prefill jit call; batched admission never starves an
earlier waiter from another bucket; and while slots decode, a tick runs
one prefill call."""
from collections import deque

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import init_params
from repro.serving import (CompileCache, Request, SamplingOpts,
                           ServingEngine)

CFG = get_config("paper-backbone").with_updates(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=300)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
# one cache for the whole module so the two modes share programs and the
# suite compiles each program exactly once
CC = CompileCache()

MODES = ("per_slot", "batched")


def _engine(mode, slots=2, cfg=CFG, params=PARAMS, cc=CC):
    return ServingEngine(cfg, params, slots=slots, max_seq=64,
                         decode_mode=mode, compile_cache=cc)


def _mixed_requests(n=6, seed=0, vocab=CFG.vocab_size):
    rng = np.random.default_rng(seed)
    lengths = [3, 10, 17, 33, 40, 5, 12, 26][:n]
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, size=lengths[i])
                    .astype(np.int32),
                    max_new_tokens=4 + i % 4)
            for i in range(n)]


def _streams(eng, reqs):
    return [tuple(r.generated) for r in reqs]


# ------------------------------------------------------------ equivalence --
def test_mixed_prompt_lengths_and_slot_recycling_match_reference():
    results = {}
    for mode in MODES:
        eng = _engine(mode, slots=2)     # 6 requests / 2 slots → recycling
        reqs = _mixed_requests()
        for r in reqs:
            eng.submit(r)
        eng.drain()
        assert all(r.done for r in reqs)
        results[mode] = (_streams(eng, reqs), eng.stats.tokens_out,
                         eng.stats.prefills, eng.stats.steps)
    assert results["batched"] == results["per_slot"]


def test_midstream_admissions_match_reference():
    results = {}
    for mode in MODES:
        eng = _engine(mode, slots=2)
        reqs = _mixed_requests(5, seed=3)
        for r in reqs[:2]:
            eng.submit(r)
        eng.step()
        eng.step()
        for r in reqs[2:]:
            eng.submit(r)
        eng.drain()
        results[mode] = _streams(eng, reqs)
    assert results["batched"] == results["per_slot"]


def test_swap_model_mid_decode_matches_reference():
    from repro.elastic import ElasticSupernet, VariantSpec
    sn = ElasticSupernet(CFG, PARAMS)
    vcfg, vparams = sn.variant(VariantSpec(depth_ratio=0.5))
    results = {}
    for mode in MODES:
        eng = _engine(mode, slots=2)
        reqs = _mixed_requests(4, seed=5)
        for r in reqs:
            r.max_new_tokens = 6
            eng.submit(r)
        eng.step()
        eng.step()
        eng.swap_model(vcfg, vparams, eng.opts)
        eng.drain()
        assert eng.generation == 1
        # in-flight requests were re-queued as copies; collect the live
        # objects the engine actually finished
        done = sorted({id(r): r for r in reqs}.values(), key=lambda r: r.rid)
        results[mode] = [tuple(r.generated[:6]) for r in done]
    assert results["batched"] == results["per_slot"]


def test_ssm_arch_matches_reference():
    cfg = get_config("mamba2-370m").reduced(d_model=64).with_updates(
        vocab_size=300, ssm_chunk=16)
    params = init_params(cfg, jax.random.PRNGKey(1))
    cc = CompileCache()
    results = {}
    for mode in MODES:
        eng = ServingEngine(cfg, params, slots=2, max_seq=64,
                            decode_mode=mode, compile_cache=cc)
        reqs = _mixed_requests(3, seed=7, vocab=cfg.vocab_size)
        for r in reqs:
            eng.submit(r)
        eng.drain()
        results[mode] = _streams(eng, reqs)
    assert results["batched"] == results["per_slot"]


# --------------------------------------------------------------- donation --
def test_donated_stacked_cache_is_not_reused():
    eng = _engine("batched")
    eng.submit(Request(rid=0, prompt=np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=8))
    eng.step()                       # admit + prefill + first decode
    old_leaves = jax.tree_util.tree_leaves(eng._cache)
    eng.step()                       # decode donates the stacked cache
    assert all(leaf.is_deleted() for leaf in old_leaves), \
        "decode step must consume (donate) the previous stacked cache"
    # the engine held no stale reference: it keeps stepping fine
    emitted = eng.step()
    assert emitted == 1


def test_slot_write_donates_previous_stacked_cache():
    eng = _engine("batched")
    old_leaves = jax.tree_util.tree_leaves(eng._cache)
    eng.submit(Request(rid=0, prompt=np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=4))
    eng.step()                       # admission writes the prefilled slot
    assert all(leaf.is_deleted() for leaf in old_leaves)


# ---------------------------------------------------------- compile cache --
def test_engines_share_programs_through_compile_cache():
    cc = CompileCache()
    streams = []
    recompiles = []
    for _ in range(2):
        eng = _engine("batched", cc=cc)
        reqs = _mixed_requests(4, seed=9)
        for r in reqs:
            eng.submit(r)
        eng.drain()
        streams.append(_streams(eng, reqs))
        recompiles.append(eng.stats.recompiles)
    assert recompiles[0] > 0          # first engine pays for the programs
    assert recompiles[1] == 0         # second engine compiles NOTHING
    assert streams[0] == streams[1]
    assert cc.hits > 0


def test_compile_domain_isolates_platforms():
    cc = CompileCache()
    e1 = ServingEngine(CFG, PARAMS, slots=2, max_seq=64,
                       compile_cache=cc, compile_domain="pixel_6_cpu")
    assert e1.stats.recompiles == 1
    e2 = ServingEngine(CFG, PARAMS, slots=2, max_seq=64,
                       compile_cache=cc, compile_domain="pixel_6_cpu")
    assert e2.stats.recompiles == 0   # same platform: shared
    e3 = ServingEngine(CFG, PARAMS, slots=2, max_seq=64,
                       compile_cache=cc, compile_domain="jetson_agx_orin")
    assert e3.stats.recompiles == 1   # other platform: own programs


# ------------------------------------------------------ batched admission --
def test_burst_admission_issues_exactly_one_prefill_call():
    """Acceptance pin: admitting a burst of k same-bucket requests runs
    ONE prefill jit call, with streams bit-identical to sequential
    per-request admission (k calls)."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, CFG.vocab_size, size=9).astype(np.int32)
               for _ in range(4)]

    def run(prefill_mode):
        eng = ServingEngine(CFG, PARAMS, slots=4, max_seq=64,
                            prefill_mode=prefill_mode, compile_cache=CC)
        reqs = [Request(rid=i, prompt=prompts[i].copy(), max_new_tokens=4)
                for i in range(4)]
        for r in reqs:
            eng.submit(r)
        eng.step()
        calls_after_admit = eng.stats.prefill_calls
        eng.drain()
        return [tuple(r.generated) for r in reqs], calls_after_admit, eng

    batched_streams, batched_calls, eng = run("batched")
    sequential_streams, sequential_calls, _ = run("per_request")
    assert batched_calls == 1
    assert sequential_calls == 4
    assert eng.stats.prefills == 4        # still one *prefill* per request
    assert batched_streams == sequential_streams


def test_per_slot_decode_forces_per_request_admission():
    eng = _engine("per_slot")
    assert eng.prefill_mode == "per_request"


def test_heterogeneous_sampling_and_mixed_bursts_share_programs():
    """Fleet regression: same-platform engines with different per-slot
    sampling policies and different admission burst sizes must find every
    program warm — sampling state and burst membership are runtime data,
    never compile keys."""
    cc = CompileCache()

    def serve(sampling_for, burst_sizes):
        eng = ServingEngine(CFG, PARAMS, slots=4, max_seq=64,
                            compile_cache=cc, compile_domain="pixel_6_cpu")
        rng = np.random.default_rng(3)
        rid = 0
        for size in burst_sizes:
            for _ in range(size):
                eng.submit(Request(
                    rid=rid, sampling=sampling_for(rid),
                    prompt=rng.integers(0, CFG.vocab_size, size=int(
                        rng.integers(4, 15))).astype(np.int32),
                    max_new_tokens=3))
                rid += 1
            eng.drain()
        return eng

    # first engine warms every (bucket, k-bucket) admission program
    e0 = serve(lambda rid: None, burst_sizes=(4, 2, 1, 3))
    assert e0.stats.recompiles > 0
    # second same-platform engine: heterogeneous per-request sampling and
    # a different burst mix — compiles NOTHING
    e1 = serve(lambda rid: SamplingOpts(temperature=0.3 * rid,
                                        top_k=rid % 3, seed=rid),
               burst_sizes=(3, 1, 4, 2))
    assert e1.stats.recompiles == 0
    assert e1.stats.sampled_tokens > 0    # the sampled slots really sampled
    # another platform still pays for its own binaries
    e2 = ServingEngine(CFG, PARAMS, slots=4, max_seq=64,
                       compile_cache=cc, compile_domain="jetson_agx_orin")
    assert e2.stats.recompiles == 1


def test_earlier_cross_bucket_waiter_is_not_starved():
    """A stream of short same-bucket arrivals can share a burst's free
    slots, but the head of the queue anchors every burst — an earlier
    waiter from another bucket is always admitted before anything
    submitted behind it."""
    rng = np.random.default_rng(13)

    def short():
        return rng.integers(0, CFG.vocab_size, size=6).astype(np.int32)

    eng = _engine("batched", slots=2)
    # occupy both slots with bucket-16 requests
    for i in range(2):
        eng.submit(Request(rid=i, prompt=short(), max_new_tokens=12))
    eng.step()
    # an odd-bucket request waits...
    other = Request(rid=100,
                    prompt=rng.integers(0, CFG.vocab_size, size=20)
                    .astype(np.int32), max_new_tokens=2)
    eng.submit(other)
    # ...while short bucket-16 requests keep arriving behind it
    late = [Request(rid=200 + i, prompt=short(), max_new_tokens=2)
            for i in range(6)]
    for r in late:
        eng.submit(r)
    eng.drain()
    assert other.done
    assert other.first_token_s is not None
    assert all(other.first_token_s < r.first_token_s for r in late)


@pytest.mark.parametrize("decode_mode", ["batched", "paged"])
def test_staged_admission_prefills_one_cohort_per_tick(decode_mode):
    """While slots decode, a tick runs one prefill call: the head's
    bucket among the new arrivals, the other buckets a tick later, ahead
    of anything newer and never in one burst with it.  An idle engine
    admits every bucket at once."""
    rng = np.random.default_rng(29)

    def req(rid, n, new=3):
        return Request(rid=rid, prompt=rng.integers(
            0, CFG.vocab_size, size=n).astype(np.int32), max_new_tokens=new)

    kw = {"block_size": 8} if decode_mode == "paged" else {}
    eng = ServingEngine(CFG, PARAMS, slots=6, max_seq=64,
                        decode_mode=decode_mode, compile_cache=CC, **kw)
    idle = [req(0, 6, new=12), req(1, 20, new=12)]     # buckets 16, 32
    for r in idle:
        eng.submit(r)
    eng.step()
    assert eng.stats.prefill_calls == 2 and all(r.generated for r in idle)

    a, b, c = req(10, 5), req(11, 30), req(12, 9)        # 16, 32, 16
    for r in (a, b, c):
        eng.submit(r)
    eng.step()
    assert eng.stats.prefill_calls == 3                   # a and c together
    assert a.generated and c.generated and not b.generated
    d = req(13, 25)                                       # 32, like b
    eng.submit(d)
    eng.step()
    assert eng.stats.prefill_calls == 4                   # b, without d
    assert b.generated and not d.generated
    eng.step()
    assert eng.stats.prefill_calls == 5 and d.generated
    eng.drain()
    assert all(r.done for r in idle + [a, b, c, d])


# -------------------------------------------------------------- scheduler --
def test_queue_is_constant_time_deque_and_fifo():
    eng = _engine("batched", slots=1)
    assert isinstance(eng._queue, deque)
    reqs = _mixed_requests(4, seed=11)
    for r in reqs:
        eng.submit(r)
    # single slot → strict FIFO: rid i must finish before rid i+1 starts
    finish_order = []
    while any(eng._active) or eng._queue:
        eng.step()
        for r in reqs:
            if r.done and r.rid not in finish_order:
                finish_order.append(r.rid)
    assert finish_order == [0, 1, 2, 3]
