"""Granite-4.0-H (Mamba-2 + NoPE GQA + MoE) on the paged path against its
plain float32 reference, on the CPU at a tiny size (``granite_tiny``).

Each tolerance is stated with its reason and checked against a control
in a lower precision than the configuration's (weights or state rounded
to bfloat16), which has to fail it.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import granite_tiny
from repro.kernels.mamba_decode import mamba_decode_step
from repro.kernels.ref import mamba_decode_step_ref
from repro.models import moe as moe_mod
from repro.models.layers import ffn_apply
from repro.models.model import (init_paged_pool, init_paged_slot_cache,
                                paged_kernel_decode_logits,
                                paged_prefill_admit)
from repro.models.runtime import DEFAULT_OPTIONS

BS = 8
MAX_SEQ = 64
BUCKET = 16
STEPS = 10
OPTS = DEFAULT_OPTIONS.replace(paged_kernel=True, kv_cache_dtype="float32")
# float32 throughout, sums in another order than the reference's (the
# chunked scan against the step-by-step recurrence, blocked attention):
# ~1e-6 of the logits' range.  Weights in bfloat16 move them ~1e-2.
LOGIT_TOL = 2e-4

_prefill = jax.jit(paged_prefill_admit, static_argnums=(1, 10))
_decode = jax.jit(paged_kernel_decode_logits, static_argnums=(1, 6))


def _prompts(k, vocab, seed=0):
    rng = np.random.default_rng(seed)
    lens = [5, 16, 11][:k]
    return [rng.integers(1, vocab, size=n).astype(np.int32) for n in lens]


def _served(cfg, params, prompts):
    """Paged prefill of the left-padded prompts into slots 0..k-1, then
    ``STEPS`` greedy decode steps.  Returns the padded rows, the tokens
    fed to the decode steps ``(k, STEPS)`` and the logits at each served
    position ``(k, STEPS + 1, vocab)``."""
    k = len(prompts)
    mb = MAX_SEQ // BS
    cache = init_paged_slot_cache(cfg, k, MAX_SEQ, OPTS)
    pool = init_paged_pool(cfg, k * mb + 1, BS, OPTS)
    tables = jnp.arange(1, k * mb + 1, dtype=jnp.int32).reshape(k, mb)
    rows = np.zeros((k, BUCKET), np.int32)
    for i, p in enumerate(prompts):
        rows[i, BUCKET - len(p):] = p
    _, last, cache, pool = _prefill(
        params, cfg, cache, pool, jnp.asarray(rows),
        jnp.arange(k, dtype=jnp.int32), jnp.zeros((k, 2), jnp.uint32),
        jnp.zeros(k), jnp.zeros(k, jnp.int32), tables[:, :BUCKET // BS],
        OPTS)
    logits, fed = [last], []
    for _ in range(STEPS):
        tok = jnp.argmax(logits[-1][:, :cfg.vocab_size], -1).astype(
            jnp.int32)
        fed.append(tok)
        lg, pool, state = _decode(params, cfg, cache, pool, tok, tables,
                                  OPTS)
        cache = dict(cache, pos=cache["pos"] + 1, **state)
        logits.append(lg)
    return (rows, np.stack(fed, 1),
            np.stack([np.asarray(x[:, :cfg.vocab_size]) for x in logits], 1))


def _reference(params, conf, rows, fed):
    ref = granite_tiny.reference()
    out = []
    for row, toks in zip(rows, fed):
        seq = np.concatenate([row, toks])
        out.append(ref.logits(params, conf, conf["num_hidden_layers"],
                              seq)[BUCKET - 1:])
    return np.stack(out)


def _rel_err(a, b):
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.fixture(scope="module")
def served():
    cfg, params = granite_tiny.model()
    prompts = _prompts(3, cfg.vocab_size)
    rows, fed, logits = _served(cfg, params, prompts)
    ref = _reference(params, granite_tiny.CONFIG, rows, fed)
    return cfg, params, prompts, rows, fed, logits, ref


def test_paged_prefill_and_decode_match_the_reference(served):
    """Prefill, then 10 decode steps through the pool and the slot state,
    against the reference's full forward over the same tokens."""
    cfg, params, _, rows, fed, logits, ref = served
    assert cfg.layer_types == ("mamba", "mamba", "attn", "mamba", "mamba")
    err = _rel_err(logits, ref)
    assert err < LOGIT_TOL, err
    # the control: the reference at the weights rounded to bfloat16
    ctl = _reference(granite_tiny.bf16(params), granite_tiny.CONFIG, rows,
                     fed)
    assert _rel_err(ctl, ref) > LOGIT_TOL


@pytest.mark.parametrize("scalar,value", [("embed_scale", 1.0),
                                          ("attn_scale", 0.25),
                                          ("residual_scale", 1.0),
                                          ("logits_scale", 1.0)])
def test_each_granite_scalar_is_exercised(served, scalar, value):
    """Served without one of the four scalars, the program leaves the
    reference's tolerance: each is part of what the parity test pins."""
    cfg, params, prompts, rows, fed, _, ref = served
    _, _, logits = _served(cfg.with_updates(**{scalar: value}), params,
                           prompts)
    assert _rel_err(logits[:, :1], ref[:, :1]) > 10 * LOGIT_TOL


def _kernel_args(seed=0, slots=3, layers=2, rows=8, n=16, t=128):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return (jax.random.normal(ks[0], (slots, layers, rows, n, t)),
            jnp.int32(1),
            jax.random.normal(ks[1], (slots, rows, t)).astype(jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(ks[2], (slots, rows, t))),
            -jnp.exp(jax.random.normal(ks[3], (rows, t))),
            jax.random.normal(ks[4], (rows, t)),
            jax.random.normal(ks[5], (slots, n, t)),
            jax.random.normal(ks[6], (slots, n, t)))


def test_mamba_decode_kernel_matches_the_jnp_update():
    """The Pallas kernel in interpret mode against the jnp update:
    float32, the same products summed over N in another order (~1e-6).
    A state rounded to bfloat16 moves it ~1e-2."""
    args = _kernel_args()
    s_ref, y_ref = mamba_decode_step_ref(*args)
    s_k, y_k = jax.jit(lambda *a: mamba_decode_step(*a, interpret=True))(
        *args)
    tol = 1e-5
    for got, want in ((s_k, s_ref), (y_k, y_ref)):
        assert _rel_err(np.asarray(got), np.asarray(want)) < tol
    # the other layer is left as it was, bit for bit
    np.testing.assert_array_equal(np.asarray(s_k[:, 0]),
                                  np.asarray(args[0][:, 0]))
    low = (args[0].astype(jnp.bfloat16).astype(jnp.float32),) + args[1:]
    s_low, y_low = mamba_decode_step_ref(*low)
    assert _rel_err(np.asarray(y_low), np.asarray(y_ref)) > tol


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of a 16-expert top-4 layer, each told which
    two experts it holds, add up (with the shared expert counted once) to
    the reference's layer with every expert: no token is dropped and no
    routing weight lost.  float32: ~1e-6 apart; bfloat16 weights ~1e-2."""
    conf = granite_tiny.config(num_experts_per_tok=4,
                               deployment={"layers_held": [1, 5],
                                           "router_experts": 16,
                                           "experts_held": list(range(16))})
    cfg, params = granite_tiny.model(conf)
    moe = jax.tree_util.tree_map(lambda a: a[0], params["mamba"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.d_model))
    ref = granite_tiny.reference()
    dims = ref._dims(conf)
    want = np.asarray(ref._moe(x, moe, dict(dims), False))
    total = ffn_apply(moe["shared"], x, gated=True, activation="silu")
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        part = dict(moe, **{w: moe[w][np.array(held)]
                            for w in ("w_gate", "w_up", "w_down")})
        total = total + moe_mod.moe_share_apply(
            part, x, cfg.with_updates(experts_held=held))
    tol = 1e-4
    assert _rel_err(np.asarray(total), want) < tol
    low = np.asarray(ref._moe(x, granite_tiny.bf16(moe), dict(dims), False))
    assert _rel_err(low, want) > tol
