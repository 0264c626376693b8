"""The paged engine's second kind of cache: a hybrid stack's per-slot
recurrent state beside the block pool (``granite_tiny``), through
freeze/thaw, ``swap_model``, prefix reuse and the donated decode step.
Each test also runs on a dense stack, whose behaviour must not change.
"""
import numpy as np
import jax
import pytest

import granite_tiny
from repro.configs import get_config
from repro.models.model import init_params
from repro.models.runtime import DEFAULT_OPTIONS
from repro.obs import TraceRecorder
from repro.obs.query import spans
from repro.serving import Request, ServingEngine
from repro.serving.compile_cache import CompileCache
from repro.serving.sampling import SamplingOpts

OPTS = DEFAULT_OPTIONS.replace(paged_kernel=True, kv_cache_dtype="float32")
SAMPLING = SamplingOpts(temperature=0.9, seed=11)


def _dense():
    cfg = get_config("paper-backbone").with_updates(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=300)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


STACKS = {"granite": granite_tiny.model, "dense": _dense}
_BUILT = {}


def _stack(name):
    if name not in _BUILT:
        _BUILT[name] = STACKS[name]()
    return _BUILT[name]


def _engine(name, cc=None, recorder=None, slots=3):
    cfg, params = _stack(name)
    kw = {} if recorder is None else {"recorder": recorder}
    return ServingEngine(cfg, params, slots=slots, max_seq=64, opts=OPTS,
                         decode_mode="paged", block_size=8,
                         sampling=SAMPLING, params_version=0,
                         compile_cache=cc or CompileCache(), **kw)


def _requests(name, n=3, seed=5):
    vocab = _stack(name)[0].vocab_size
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(1, vocab, size=m)
                    .astype(np.int32), max_new_tokens=b)
            for i, (m, b) in enumerate([(7, 14), (13, 12), (4, 10)][:n])]


def _uninterrupted(name):
    eng = _engine(name)
    reqs = _requests(name)
    for r in reqs:
        eng.submit(r)
    eng.drain()
    return [r.generated for r in reqs]


@pytest.mark.parametrize("name", list(STACKS))
def test_freeze_and_thaw_onto_another_slot_continue_the_stream(name):
    """Freeze a request mid-decode, let another request take its slot,
    thaw it into a third: every stream is bit-identical to the run that
    was never interrupted."""
    want = _uninterrupted(name)
    rec = TraceRecorder()
    eng = _engine(name, recorder=rec)
    reqs = _requests(name, n=2)
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    slot_before = eng._active.index(reqs[0])
    frozen = eng.freeze(reqs[0].rid)
    assert frozen is reqs[0]
    blob = frozen.frozen
    filler = _requests(name, n=3)[2]
    eng.submit(filler)
    eng.step()                                  # the filler takes the slot
    assert eng._active[slot_before] is filler
    eng.thaw(frozen)
    eng.step()
    assert eng._active.index(reqs[0]) != slot_before
    eng.drain()
    assert [r.generated for r in reqs] == want[:2]
    assert filler.generated == want[2]
    assert eng.stats.thaws == 1 and eng.stats.prefill_calls == 2
    state = spans(rec, name="engine.state")
    if name == "granite":
        assert {"ssm", "conv"} <= set(blob.leaves)
        assert len(state) >= 3        # admissions, the freeze, the thaw
    else:
        assert not {"ssm", "conv"} & set(blob.leaves)
        assert state == []


@pytest.mark.parametrize("name", list(STACKS))
def test_swap_model_mid_decode_keeps_the_state(name):
    """A same-weights ``swap_model`` mid-decode freezes every request,
    rebuilds the caches and thaws them: the streams do not change."""
    want = _uninterrupted(name)
    eng = _engine(name)
    reqs = _requests(name)
    for r in reqs:
        eng.submit(r)
    for _ in range(5):
        eng.step()
    cfg, params = _stack(name)
    eng.swap_model(cfg, params, OPTS, params_version=0)
    eng.drain()
    assert [r.generated for r in reqs] == want
    assert eng.stats.thaws == 3


@pytest.mark.parametrize("name", list(STACKS))
def test_duplicate_prompt_reuses_blocks_only_without_state(name):
    """A second request with an already-prefilled prompt: a dense stack
    reuses the cached prefill; a recurrent one is prefilled again, counted
    by ``engine.prefix_refused_recurrent``, and serves what a fresh engine
    serves."""
    first, again = _requests(name, n=2)
    again.prompt = first.prompt.copy()
    eng = _engine(name)
    eng.submit(first)
    eng.drain()
    calls = eng.stats.prefill_calls
    eng.submit(again)
    eng.drain()
    refused = eng.metrics.counter("engine.prefix_refused_recurrent").value
    fresh = _engine(name)
    twin = Request(rid=again.rid, prompt=again.prompt.copy(),
                   max_new_tokens=again.max_new_tokens)
    fresh.submit(twin)
    fresh.drain()
    assert again.generated == twin.generated
    if name == "granite":
        assert refused == 1 and eng.stats.prefill_calls == calls + 1
    else:
        assert refused == 0 and eng.stats.prefill_calls == calls


@pytest.mark.parametrize("name", list(STACKS))
def test_decode_program_donates_the_state(name):
    """The decode step's input buffers are deleted after it: the slot
    state (recurrent stacks) and the pool are updated in place, and the
    outputs keep the inputs' shapes and dtypes, so the next step runs
    the same program."""
    eng = _engine(name)
    for r in _requests(name, n=2):
        eng.submit(r)
    eng.step()
    leaves = {n: eng._cache[n] for n in ("ssm", "conv") if n in eng._cache}
    assert (name == "granite") == bool(leaves)
    pool_k = eng._pool["k"]
    avals = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                   (eng._cache, eng._pool))
    eng.step()
    assert pool_k.is_deleted()
    assert all(a.is_deleted() for a in leaves.values())
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                  (eng._cache, eng._pool)) == avals
    state_bytes = eng.metrics.gauge("engine.state_bytes").value
    if name == "granite":
        per_slot = sum(a.nbytes for a in leaves.values()) // eng.slots
        assert state_bytes == 2 * per_slot
    else:
        assert state_bytes is None
