"""End-to-end suite for the paged decode kernel path and int8 KV pools.

* ``paged_kernel=True`` routes paged decode through the block-table
  attention op (no gather-to-dense detour).  Token streams must match
  the dense batched decode on the same request mixes the paging suite
  uses — the op's oracle runs in f32 like the gather path, so equality
  is bit-exact, not approx.
* ``kv_dtype="int8"`` stores the pool int8 with per-row scales.  Greedy
  streams must match the f32-pool greedy streams (quantization error
  must not flip an argmax on the differential corpus), on both the
  gather and kernel paths.
* Block tables stay runtime data with the kernel on: second waves,
  fragmented pools and second engines cost zero recompiles.
* Freeze/thaw: int8-pool blobs are densified in ``kv_cache_dtype`` and
  therefore portable — same-engine round-trips are exact, and
  cross-``kv_dtype`` migration thaws with zero re-prefill and zero
  token loss (continuation decodes with the destination's numerics).
"""
import dataclasses

import pytest

import jax
import numpy as np

from repro.configs import get_config
from repro.engine.schedule import EngineConfig
from repro.launch.serve import build, make_requests, serve
from repro.models.model import init_params
from repro.models.runtime import DEFAULT_OPTIONS
from repro.serving import (CompileCache, Request, SamplingOpts,
                           ServingEngine)
from repro.serving import compile_cache as cc_mod
from repro.serving.paging import TRASH_BLOCK

CFG = get_config("paper-backbone").with_updates(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=300)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
MAX_SEQ = 64
CC = CompileCache()

KERNEL = dataclasses.replace(DEFAULT_OPTIONS, paged_kernel=True)
INT8 = dataclasses.replace(DEFAULT_OPTIONS, kv_dtype="int8")
KERNEL_INT8 = dataclasses.replace(DEFAULT_OPTIONS, paged_kernel=True,
                                  kv_dtype="int8")

# the paging suite's deterministic mixes (prompt len, budget, admit
# step, temperature); the greedy corpus drops temperature for the int8
# argmax-stability checks
MIX_CORPUS = [
    [(1, 1, 0, 0.0)],
    [(40, 6, 0, 0.8)],
    [(5, 4, 0, 0.0), (20, 4, 1, 0.8), (33, 3, 2, 1.4), (9, 2, 2, 0.0)],
    [(16, 3, 0, 1.4), (16, 3, 0, 1.4), (17, 3, 3, 0.8)],
]
# greedy mixes for the int8 argmax-stability checks: a tiny random-weight
# model has near-tied logits, so the corpus pins mixes whose argmax
# margins survive the quantization error envelope (<0.05 on attention
# outputs) on BOTH the gather and kernel paths — single-token, long
# prompt, duplicate prompts (prefix sharing), staggered admits
GREEDY_CORPUS = [
    [(1, 1, 0, 0.0)],
    [(40, 6, 0, 0.0)],
    [(16, 3, 0, 0.0), (16, 3, 0, 0.0), (17, 3, 3, 0.0)],
    [(9, 6, 0, 0.0), (25, 6, 0, 0.0)],
    [(12, 5, 0, 0.0), (30, 4, 1, 0.0)],
]


def _prompt(length, rid):
    rng = np.random.default_rng(31 * length + rid)
    return rng.integers(0, CFG.vocab_size, size=length).astype(np.int32)


def _requests(mix, rid_base=0):
    return [Request(rid=rid_base + i, prompt=_prompt(n, rid_base + i),
                    max_new_tokens=budget,
                    sampling=SamplingOpts(temperature=temp, seed=5))
            for i, (n, budget, _, temp) in enumerate(mix)]


def _engine(cfg=CFG, params=PARAMS, **kw):
    kw.setdefault("slots", 2)
    return ServingEngine(cfg, params, max_seq=MAX_SEQ, compile_cache=CC,
                         **kw)


def _drive(eng, reqs, mix, max_steps=200):
    step = 0
    while any(not r.done for r in reqs):
        for r, (_, _, at, _) in zip(reqs, mix):
            if at == step:
                eng.submit(r)
        eng.step()
        step += 1
        assert step < max_steps, "engine failed to drain"
    return [tuple(r.generated) for r in reqs]


def _run(mix, *, rid_base=0, **kw):
    eng = _engine(**kw)
    reqs = _requests(mix, rid_base)
    return _drive(eng, reqs, mix), eng


_DENSE = {}


def _dense_baseline(mix, model=None):
    key = (tuple(mix), model)
    if key not in _DENSE:
        _DENSE[key] = _run(mix, decode_mode="batched", **_model(model))[0]
    return _DENSE[key]


def _model(name):
    """Engine keywords for a model other than ``CFG``: gemma3 at two
    whole 6-layer periods plus one layer, its window cut below the
    prompts so local and global layers differ."""
    if name is None:
        return {}
    cfg = get_config(name).reduced(num_layers=13).with_updates(
        sliding_window=8)
    return {"cfg": cfg, "params": init_params(cfg, jax.random.PRNGKey(0))}


# ----------------------------------------------- kernel ≡ dense batched --
@pytest.mark.parametrize(
    "mix,block_size,model",
    [pytest.param(mix, bs, None, id=f"{i}-{bs}")
     for i, mix in enumerate(MIX_CORPUS) for bs in (4, 8, 16)]
    + [pytest.param(MIX_CORPUS[2], 8, "gemma3-12b", id="gemma3-13l-2-8")])
def test_kernel_paged_matches_dense_batched(mix, block_size, model):
    streams, eng = _run(mix, decode_mode="paged", block_size=block_size,
                        opts=KERNEL, **_model(model))
    assert streams == _dense_baseline(mix, model)
    assert (eng.block_pool.tables == TRASH_BLOCK).all()


# --------------------------------------------------- int8 greedy parity --
@pytest.mark.parametrize("opts", [INT8, KERNEL_INT8],
                         ids=["gather_int8", "kernel_int8"])
@pytest.mark.parametrize("mix", GREEDY_CORPUS,
                         ids=range(len(GREEDY_CORPUS)))
def test_int8_pool_greedy_matches_f32(mix, opts):
    """Per-row int8 KV must not flip a greedy argmax on the corpus."""
    streams, _ = _run(mix, decode_mode="paged", opts=opts)
    assert streams == _dense_baseline(mix)


def test_int8_pool_allocates_scale_leaves():
    eng = _engine(decode_mode="paged", opts=INT8)
    pool = eng._pool
    assert pool["k"].dtype == np.dtype("int8")
    assert pool["v"].dtype == np.dtype("int8")
    assert "k_scale" in pool and "v_scale" in pool
    assert pool["k_scale"].dtype == np.dtype("float32")


def test_kv_dtype_validation():
    with pytest.raises(ValueError):
        _engine(decode_mode="paged",
                opts=dataclasses.replace(DEFAULT_OPTIONS, kv_dtype="int3"))
    # pool-only options are rejected on dense engines
    for opts in (INT8, KERNEL):
        with pytest.raises(ValueError):
            _engine(decode_mode="batched", opts=opts)


# ------------------------------------------------- recompiles stay zero --
@pytest.mark.parametrize("opts", [KERNEL, KERNEL_INT8],
                         ids=["kernel", "kernel_int8"])
def test_kernel_no_recompiles_across_occupancy(opts):
    """Block tables stay runtime data with the kernel on: fragmented
    second waves and fresh same-geometry engines compile nothing."""
    mix = MIX_CORPUS[2]
    eng = _engine(decode_mode="paged", opts=opts)
    _drive(eng, _requests(mix), mix)
    warm = eng.stats.recompiles
    _drive(eng, _requests(mix, rid_base=100), mix)
    assert eng.stats.recompiles == warm

    eng2 = _engine(decode_mode="paged", opts=opts)
    _drive(eng2, _requests(mix, rid_base=200), mix)
    assert eng2.stats.recompiles == 0


# ------------------------------------------------------------ freeze/thaw --
def _freeze_after(eng, reqs, mix, steps):
    for r, (_, _, at, _) in zip(reqs, mix):
        assert at == 0
        eng.submit(r)
    for _ in range(steps):
        eng.step()
    moved = eng.freeze_all("migrate") + eng.drain_waiting()
    assert not eng.has_work
    return moved


def test_int8_freeze_thaw_same_engine_is_exact():
    mix = [(9, 6, 0, 1.2), (25, 6, 0, 0.0)]
    baseline, _ = _run(mix, decode_mode="paged", opts=KERNEL_INT8)
    eng = _engine(decode_mode="paged", opts=KERNEL_INT8)
    reqs = _requests(mix)
    moved = _freeze_after(eng, reqs, mix, steps=3)
    for r in moved:
        assert eng.thaw(r)
    eng.drain()
    assert [tuple(r.generated) for r in reqs] == baseline


@pytest.mark.parametrize("dst_opts", [DEFAULT_OPTIONS, KERNEL, INT8],
                         ids=["gather_bf16", "kernel_bf16", "gather_int8"])
def test_cross_kv_dtype_migration_zero_reprefill(dst_opts):
    """Blobs are densified in ``kv_cache_dtype``, so pool-storage
    options are normalized out of the thaw fingerprint: an int8-pool
    source migrates onto bf16 and int8 destinations with zero
    re-prefill and zero token loss (continuations decode with the
    destination's numerics, so only the earned prefix is pinned)."""
    mix = [(9, 6, 0, 0.0), (25, 6, 0, 0.0)]
    src = _engine(decode_mode="paged", opts=KERNEL_INT8)
    reqs = _requests(mix)
    moved = _freeze_after(src, reqs, mix, steps=3)
    earned = {r.rid: tuple(r.generated) for r in moved}
    assert any(r.frozen is not None for r in moved)

    dst = _engine(decode_mode="paged", opts=dst_opts)
    calls = dst.stats.prefill_calls
    for r in moved:
        assert dst.thaw(r)
    dst.drain()
    assert dst.stats.prefill_calls == calls         # zero re-prefill
    for r, (_, budget, _, _) in zip(reqs, mix):
        assert tuple(r.generated)[:len(earned[r.rid])] == earned[r.rid]
        assert len(r.generated) == budget           # full budget, no loss


# ------------------------------------------------- options through a swap --
@pytest.mark.parametrize("opts", [KERNEL, INT8, KERNEL_INT8],
                         ids=["kernel", "int8", "kernel_int8"])
def test_swap_keeps_pool_options_left_at_defaults(opts):
    """Variant options built over the engine's own, as the middleware
    builds them, keep its ``paged_kernel``/``kv_dtype``, which a
    variant's engine config leaves at their defaults; options passed
    whole replace them, either way."""
    eng = _engine(decode_mode="paged", opts=opts)
    vopts = EngineConfig(remat_policy="dots").to_runtime_options(eng.opts)
    eng.swap_model(CFG, PARAMS, vopts)
    assert eng.opts == opts.replace(remat="dots")
    eng.swap_model(CFG, PARAMS, DEFAULT_OPTIONS)
    assert eng.opts == DEFAULT_OPTIONS
    eng.swap_model(CFG, PARAMS, KERNEL_INT8)
    assert eng.opts == KERNEL_INT8


def test_kernel_step_survives_middleware_swap(monkeypatch):
    """The serving driver's adaptation loop swaps in a variant whose
    options come from the middleware; the swapped-in variant's decode
    program must still be the kernel step."""
    traced = []
    real = cc_mod.paged_kernel_sample_batched_step

    def spy(params, cfg, *args):
        traced.append(cfg)
        return real(params, cfg, *args)

    monkeypatch.setattr(cc_mod, "paged_kernel_sample_batched_step", spy)
    engine, mw = build(CFG, slots=2, max_seq=MAX_SEQ, decode_mode="paged",
                       compile_cache=CompileCache())
    reqs = make_requests(CFG.vocab_size, 6, prompt_lens=(8, 30),
                         max_new_tokens=8)
    serve(engine, mw, reqs, adapt_every=4, log=lambda _: None)
    assert engine.generation >= 1 and engine.cfg != CFG
    assert engine.opts.paged_kernel
    assert traced[0] == CFG and traced[-1] == engine.cfg
    assert sum(len(r.generated) for r in reqs) == 6 * 8
