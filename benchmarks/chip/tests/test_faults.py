"""The output check against faults planted under the timed path: a run
whose served tokens are wrong must come out not correct.

Of the faults a serving cell can have: a token altered where it is
produced, and a decode step that returns its state (the KV pool)
unchanged.  Half a batch left out and a missing exchange between chips
are faults of training and of cells on several chips; no cell here has
them."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import tiny


def _run():
    args = argparse.Namespace(workload=None, seed=11, seconds=2.0, trace=0)
    return run.execute(args, require_tpu=False, bench=tiny.bench(),
                       loaded=tiny.cell(), out=lambda *a, **k: None)


@pytest.fixture
def engine_cls():
    from repro.serving import ServingEngine
    return ServingEngine


def test_sound_run_is_correct():
    assert _run()["correct"] is True


def test_token_altered_where_produced(monkeypatch, engine_cls):
    real = engine_cls._bookkeep_decode
    calls = {"n": 0}

    def altered(self, nxt, pos):
        nxt = np.array(jax.device_get(nxt))
        calls["n"] += 1
        if calls["n"] % 5 == 3:
            # every slot's token, so whichever requests the check samples
            # carry altered tokens
            nxt = (nxt + 1) % self.cfg.vocab_size
        return real(self, nxt, pos)

    monkeypatch.setattr(engine_cls, "_bookkeep_decode", altered)
    result = _run()
    assert calls["n"] > 3
    assert result["correct"] is False
    assert result["check"]["max_gap"]["value"] > \
        result["check"]["max_gap"]["limit"]


def test_decode_step_leaves_its_state_unchanged(monkeypatch, engine_cls):
    real = engine_cls._paged_decode_fn

    def stale(self):
        fn = real(self)

        def step(params, cache, pool, tokens, tables):
            kept = jax.tree_util.tree_map(jnp.copy, pool)
            nxt, pos, cache, _ = fn(params, cache, pool, tokens, tables)
            return nxt, pos, cache, kept
        return step

    monkeypatch.setattr(engine_cls, "_paged_decode_fn", stale)
    result = _run()
    assert result["correct"] is False
