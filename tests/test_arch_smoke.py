"""Per-architecture smoke tests: reduced same-family variants run one
forward + one train step + one decode step on CPU, asserting output shapes
and the absence of NaNs.  (The FULL configs are exercised only via the
dry-run — ShapeDtypeStruct, no allocation.)"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_archs
from repro.models import (RuntimeOptions, decode_step, forward, init_cache,
                          init_params, lm_loss, prefill)

OPTS = RuntimeOptions(moe_capacity_factor=2.0)


def _inputs(cfg, key, batch=2, seq=16):
    kw = {}
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    if cfg.is_encoder_decoder:
        kw["encoder_frames"] = jax.random.normal(
            key, (batch, cfg.encoder_seq_len, cfg.d_model)) * 0.1
    if cfg.vision_embed_dim:
        kw["vision_embeds"] = jax.random.normal(
            key, (batch, cfg.num_vision_tokens, cfg.vision_embed_dim)) * 0.1
    return tokens, kw


@pytest.mark.parametrize("arch", list_archs())
def test_forward_smoke(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    tokens, kw = _inputs(cfg, key)
    logits, aux = forward(params, cfg, tokens, OPTS, **kw)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    assert not bool(jnp.isnan(aux).any())


@pytest.mark.parametrize("arch", list_archs())
def test_train_step_smoke(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    tokens, kw = _inputs(cfg, key)
    labels = jnp.roll(tokens, -1, axis=1)

    def loss_fn(p):
        logits, aux = forward(p, cfg, tokens, OPTS, **kw)
        return lm_loss(logits, labels) + cfg.router_aux_weight * aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert jnp.isfinite(loss)
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in flat)
    # at least one nonzero gradient per major component
    total = sum(float(jnp.abs(g.astype(jnp.float32)).sum()) for g in flat)
    assert total > 0


@pytest.mark.parametrize("arch", list_archs())
def test_decode_smoke(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(2)
    params = init_params(cfg, key)
    tokens, kw = _inputs(cfg, key, seq=8)
    cache = init_cache(cfg, 2, 32, OPTS)
    logits, cache = prefill(params, cfg, tokens, cache, OPTS, **kw)
    assert int(cache["pos"]) == 8
    lg, cache = decode_step(params, cfg, cache, tokens[:, -1], OPTS)
    assert lg.shape == (2, cfg.vocab_size)
    assert not bool(jnp.isnan(lg.astype(jnp.float32)).any())
    assert int(cache["pos"]) == 9


# stacks whose pattern period is longer than one layer, deep enough for
# two whole periods (scanned) and a remainder (unrolled after them); the
# window is cut below the prompt so local and global layers differ
DEEP = [pytest.param("gemma3-12b", 13, {"sliding_window": 4},
                     id="gemma3-12b-13l"),
        pytest.param("zamba2-1.2b", 5, {"shared_attn_period": 2},
                     id="zamba2-1.2b-5l")]


@pytest.mark.parametrize(
    "arch,num_layers,updates",
    [pytest.param(a, 2, {}, id=a) for a in list_archs()] + DEEP)
def test_decode_matches_forward(arch, num_layers, updates):
    """Decode with cache must agree with full forward at the last position
    (capacity set high enough that MoE drops nothing)."""
    cfg = get_config(arch).reduced(num_layers=num_layers).with_updates(
        **updates)
    opts = RuntimeOptions(moe_capacity_factor=8.0)
    key = jax.random.PRNGKey(3)
    params = init_params(cfg, key)
    tokens, kw = _inputs(cfg, key, seq=12)
    logits, _ = forward(params, cfg, tokens, opts, **kw)
    cache = init_cache(cfg, 2, 24, opts)
    _, cache = prefill(params, cfg, tokens[:, :11], cache, opts, **kw)
    lg, _ = decode_step(params, cfg, cache, tokens[:, 11], opts)
    ref = logits[:, -1].astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(ref - lg.astype(jnp.float32)))) / (
        float(jnp.max(jnp.abs(ref))) + 1e-9)
    assert rel < 0.06, f"{arch}: decode diverges from forward (rel={rel})"


def test_decode_scans_whole_periods():
    """Whole pattern periods are scanned, not unrolled: gemma3's decode
    step at 4 periods + 1 layer holds as many dots as at 2 periods + 1."""
    def dots(num_layers):
        cfg = get_config("gemma3-12b").reduced(num_layers=num_layers)
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        cache = jax.eval_shape(lambda: init_cache(cfg, 2, 32, OPTS))
        tok = jax.ShapeDtypeStruct((2,), jnp.int32)
        text = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t, OPTS)
                       ).lower(params, cache, tok).as_text()
        return text.count("stablehlo.dot_general")

    assert dots(25) == dots(13) > 0


def test_stack_matches_plain_layer_loop():
    """The period walk (two scanned periods, one layer after them) equals
    a plain loop over gemma3's layers, each layer's kind by its index:
    5 local then 1 global."""
    from repro.models.transformer import apply_stack, transformer_block
    cfg = get_config("gemma3-12b").reduced(num_layers=13).with_updates(
        sliding_window=4, param_dtype="float32", activation_dtype="float32")
    key = jax.random.PRNGKey(4)
    layers = init_params(cfg, key)["layers"]
    x = jax.random.normal(key, (2, 12, cfg.d_model))
    out, _ = apply_stack(layers, x, cfg, OPTS)
    ref = x
    for i in range(cfg.num_layers):
        layer = jax.tree_util.tree_map(lambda a: a[i], layers)
        window = 0 if i % 6 == 5 else cfg.sliding_window
        ref, _ = transformer_block(layer, ref, cfg, OPTS, window=window)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-4 * float(
        jnp.max(jnp.abs(ref)))
