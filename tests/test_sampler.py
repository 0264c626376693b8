"""The batched sampler (``sample_rows``) against the sampler it replaced.

``_oracle`` is the single-row body ``sample_logits`` had before the
sampler learned to branch: a stable argsort of the row, its inverse
permutation by scatter, a rank mask, a Gumbel-max, then a ``where`` back
to the argmax.  The batched sampler must return the same tokens and the
same advanced keys bit for bit, row by row, whichever branch its batch
takes; and the engine must keep a batch of greedy rows on the argmax
branch, counting each decode tick and prefill call by the branch it took.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import (SAMPLER_BRANCHES, init_params,
                                sample_logits, sample_rows,
                                sampler_branch_index)
from repro.serving import CompileCache, Request, SamplingOpts, ServingEngine
from repro.serving.sampling import sampler_branch

VOCAB, PADDED = 37, 40                 # logits rows carry padding columns


def _oracle(logits, key, temp, top_k, vocab):
    lg = logits[:vocab]
    greedy = jnp.argmax(lg).astype(jnp.int32)
    key, sub = jax.random.split(key)
    scaled = lg.astype(jnp.float32) / jnp.maximum(
        temp.astype(jnp.float32), 1e-6)
    order = jnp.argsort(-scaled)
    ranks = jnp.zeros_like(order).at[order].set(jnp.arange(vocab))
    masked = jnp.where((top_k > 0) & (ranks >= jnp.clip(top_k, 1, vocab)),
                       jnp.finfo(jnp.float32).min, scaled)
    sampled = jax.random.categorical(sub, masked).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy), key


ORACLE = jax.jit(jax.vmap(lambda lg, k, t, tk: _oracle(lg, k, t, tk,
                                                        VOCAB)))
SAMPLER = jax.jit(lambda lg, k, t, tk: sample_rows(lg, k, t, tk, VOCAB))


def _tied_rows(n: int, seed: int) -> np.ndarray:
    """Rows dense in ties: few distinct values, tied maxima, ties at the
    k-th value, signed zeros side by side, and some continuous rows."""
    rng = np.random.default_rng(seed)
    lg = rng.integers(-3, 3, size=(n, PADDED)).astype(np.float32) * 0.5
    lg[::5, :6] = lg[::5].max(axis=1, keepdims=True)      # tied maxima
    lg[1::7, :4], lg[1::7, 4:8] = 0.0, -0.0
    lg[2::9] = rng.normal(size=lg[2::9].shape)
    return lg


def _keys(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 2 ** 32, size=(n, 2), dtype=np.uint32)


def _assert_same(logits, keys, temps, top_ks):
    want_tok, want_key = ORACLE(logits, keys, temps, top_ks)
    got_tok, got_key = SAMPLER(logits, keys, temps, top_ks)
    np.testing.assert_array_equal(np.asarray(got_tok), np.asarray(want_tok))
    np.testing.assert_array_equal(np.asarray(got_key), np.asarray(want_key))


@pytest.mark.parametrize("top_k", [0, 1, 5, VOCAB - 1, VOCAB, VOCAB + 7])
@pytest.mark.parametrize("temp", [0.0, 0.6, 1.7])
def test_uniform_batch_matches_oracle(temp, top_k):
    n = 64
    _assert_same(_tied_rows(n, seed=top_k), _keys(n, seed=1),
                 np.full(n, temp, np.float32), np.full(n, top_k, np.int32))


@pytest.mark.parametrize("seed", range(4))
def test_mixed_batch_matches_oracle(seed):
    """Greedy, temperature-only and top-k rows in one call."""
    n = 240
    rng = np.random.default_rng(seed)
    temps = rng.choice(np.array([0.0, 0.6, 1.7], np.float32), n)
    top_ks = rng.choice(np.array([0, 1, 5, VOCAB - 1, VOCAB, VOCAB + 7],
                                 np.int32), n)
    _assert_same(_tied_rows(n, seed), _keys(n, seed), temps, top_ks)


@pytest.mark.parametrize("temp,top_k", [(0.0, 5), (1.7, 0), (0.6, 5)])
def test_single_row_entry_matches_its_row_in_a_batch(temp, top_k):
    n = 8
    logits, keys = _tied_rows(n, seed=3), _keys(n, seed=4)
    temps = np.full(n, temp, np.float32)
    top_ks = np.full(n, top_k, np.int32)
    batch_tok, batch_key = SAMPLER(logits, keys, temps, top_ks)
    one = jax.jit(lambda lg, k, t, tk: sample_logits(lg, k, t, tk, VOCAB))
    for i in range(n):
        tok, key = one(logits[i], keys[i], temps[i], top_ks[i])
        assert int(tok) == int(batch_tok[i])
        np.testing.assert_array_equal(np.asarray(key),
                                      np.asarray(batch_key[i]))


@pytest.mark.parametrize("policies,branch", [
    ([SamplingOpts()], "argmax"),
    ([SamplingOpts(top_k=5), SamplingOpts(top_k=VOCAB + 1)], "argmax"),
    ([SamplingOpts(), SamplingOpts(temperature=0.6)], "temperature"),
    ([SamplingOpts(temperature=1.7, top_k=VOCAB)], "temperature"),
    ([SamplingOpts(top_k=5), SamplingOpts(temperature=0.6)], "temperature"),
    ([SamplingOpts(), SamplingOpts(temperature=0.6, top_k=5)], "top_k"),
    ([SamplingOpts(temperature=1.7, top_k=VOCAB - 1)], "top_k"),
    ([SamplingOpts(temperature=1e-50, top_k=5)], "argmax"),
])
def test_host_and_device_pick_the_same_branch(policies, branch):
    assert sampler_branch(policies, VOCAB) == branch
    temps = jnp.asarray([p.temperature for p in policies], jnp.float32)
    top_ks = jnp.asarray([p.top_k for p in policies], jnp.int32)
    index = int(sampler_branch_index(temps, top_ks, VOCAB))
    assert SAMPLER_BRANCHES[index] == branch


# ------------------------------------------------------------ the engine --
CFG = get_config("paper-backbone").with_updates(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=300)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
CC = CompileCache()


def _prompt(length: int, rid: int) -> np.ndarray:
    rng = np.random.default_rng(7 * length + rid)
    return rng.integers(0, CFG.vocab_size, size=length).astype(np.int32)


def _counts(eng):
    return {b: eng.metrics.counter(f"engine.sampler.{b}").value
            for b in SAMPLER_BRANCHES}


def _serve(decode_mode, sampled):
    """Two requests admitted together in one burst: ``sampled`` (budget
    3) beside a greedy one (budget 8), on two slots."""
    eng = ServingEngine(CFG, PARAMS, slots=2, max_seq=64,
                        decode_mode=decode_mode, compile_cache=CC)
    reqs = [Request(rid=0, prompt=_prompt(9, 0), max_new_tokens=3,
                    sampling=sampled),
            Request(rid=1, prompt=_prompt(11, 1), max_new_tokens=8)]
    for r in reqs:
        eng.submit(r)
    eng.drain()
    return eng, reqs


@pytest.mark.parametrize("decode_mode", ["batched", "paged"])
@pytest.mark.parametrize("sampled,branch", [
    (SamplingOpts(temperature=0.9, seed=2), "temperature"),
    (SamplingOpts(temperature=0.9, top_k=5, seed=2), "top_k")])
def test_freed_sampling_slot_leaves_greedy_batch_on_argmax(
        decode_mode, sampled, branch):
    """The sampling request leaves after two ticks; the greedy one decodes
    five more ticks alone, on the argmax branch, its freed neighbour's
    temperature zeroed on the device."""
    eng, reqs = _serve(decode_mode, sampled)
    assert [len(r.generated) for r in reqs] == [3, 8]
    assert eng.stats.prefill_calls == 1 and eng.stats.steps == 7
    assert _counts(eng) == {"argmax": 5, branch: 3,
                            **{b: 0 for b in SAMPLER_BRANCHES
                               if b not in ("argmax", branch)}}
    sample = eng._cache["sample"]
    np.testing.assert_array_equal(np.asarray(sample["temp"]), 0.0)
    assert SAMPLER_BRANCHES[int(sampler_branch_index(
        sample["temp"], sample["top_k"], CFG.vocab_size))] == "argmax"
    # the greedy stream is the one it reads beside another greedy request
    _, alone = _serve(decode_mode, SamplingOpts())
    assert reqs[1].generated == alone[1].generated


@pytest.mark.parametrize("decode_mode,prefill_mode", [
    ("batched", "batched"), ("paged", "batched"),
    ("batched", "per_request")])
@pytest.mark.parametrize("sampling,branch", [
    (SamplingOpts(), "argmax"),
    (SamplingOpts(temperature=1.3, seed=4), "temperature"),
    (SamplingOpts(temperature=1.3, top_k=CFG.vocab_size, seed=4),
     "temperature"),
    (SamplingOpts(temperature=1.3, top_k=7, seed=4), "top_k")])
def test_sampler_counters_count_ticks_and_prefill_calls(
        decode_mode, prefill_mode, sampling, branch):
    eng = ServingEngine(CFG, PARAMS, slots=2, max_seq=64,
                        decode_mode=decode_mode, prefill_mode=prefill_mode,
                        sampling=sampling, compile_cache=CC)
    for rid, (n, budget) in enumerate([(5, 6), (20, 4), (12, 5)]):
        eng.submit(Request(rid=rid, prompt=_prompt(n, rid),
                           max_new_tokens=budget))
    eng.drain()
    decode_ticks = eng.stats.steps          # a slot is busy on every tick
    assert _counts(eng) == {
        b: (decode_ticks + eng.stats.prefill_calls if b == branch else 0)
        for b in SAMPLER_BRANCHES}
