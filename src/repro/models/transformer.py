"""Unified decoder stacks: dense / MoE / SSM / hybrid / enc-dec / VLM.

Layer stacks are ``lax.scan``-ed over *stacked* per-layer parameters so that
compile time and HLO size are O(1) in depth.  Heterogeneous repeating layer
patterns (gemma3's 5 local : 1 global, zamba2's mamba-blocks + shared
attention) are handled by scanning over pattern *periods* and unrolling the
(static) period internally.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .configs import ATTN, LOCAL, MAMBA, SHARED_ATTN, ModelConfig
from .layers import (Params, dtype_of, embed_init, embed_lookup, ffn_apply,
                     ffn_init, mask_padded_logits_raw, rms_norm, unembed)
from .runtime import DEFAULT_OPTIONS, RuntimeOptions


# ----------------------------------------------------------------- init ----
def _attn_layer_init(key, cfg: ModelConfig, dtype, cross: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    hd = cfg.resolved_head_dim
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": attn_mod.attn_init(ks[0], cfg.d_model, cfg.num_heads,
                                   cfg.num_kv_heads, hd, dtype, cfg.qkv_bias),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.arch_type == "moe":
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    else:
        p["ffn"] = ffn_init(ks[1], cfg.d_model, cfg.d_ff,
                            gated=cfg.gated_ffn, dtype=dtype)
    if cross:
        p["ln_cross"] = jnp.zeros((cfg.d_model,), dtype)
        p["cross"] = attn_mod.attn_init(ks[2], cfg.d_model, cfg.num_heads,
                                        cfg.num_kv_heads, hd, dtype, False)
    return p


def _mamba_layer_init(key, cfg: ModelConfig, dtype) -> Params:
    return {
        "ln": jnp.zeros((cfg.d_model,), dtype),
        "mamba": ssm_mod.mamba_init(key, cfg, dtype),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dtype = dtype_of(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    layer_keys = jax.random.split(keys[1], max(cfg.num_layers, 1))
    if cfg.arch_type in ("ssm", "hybrid"):
        params["layers"] = jax.vmap(
            lambda k: _mamba_layer_init(k, cfg, dtype))(layer_keys)
        if cfg.arch_type == "hybrid":
            params["shared_attn"] = _attn_layer_init(keys[2], cfg, dtype)
    else:
        cross = cfg.is_encoder_decoder
        params["layers"] = jax.vmap(
            lambda k: _attn_layer_init(k, cfg, dtype, cross=cross))(layer_keys)
    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(keys[3], cfg.encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: _attn_layer_init(k, cfg, dtype))(enc_keys)
        params["encoder_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if cfg.vision_embed_dim:
        params["vision_proj"] = {
            "w": (jax.random.normal(keys[4], (cfg.vision_embed_dim, cfg.d_model))
                  / jnp.sqrt(cfg.vision_embed_dim)).astype(dtype),
            "b": jnp.zeros((cfg.d_model,), dtype),
        }
    return params


# ----------------------------------------------------------- block apply ---
def _select_impl(cfg: ModelConfig, opts: RuntimeOptions, s: int, window: int
                 ) -> str:
    impl = opts.attn_impl
    if impl != "auto":
        return impl
    if window and s > 2 * window and s % min(opts.q_chunk, s) == 0:
        return "banded"
    if s > 1024 and s % min(opts.q_chunk, s) == 0 and s % min(opts.k_chunk, s) == 0:
        return "chunked"
    return "full"


def attn_block(layer: Params, x: jax.Array, cfg: ModelConfig,
               opts: RuntimeOptions, *, window: int, causal: bool = True
               ) -> jax.Array:
    s = x.shape[1]
    h = attn_mod.attention_block(
        layer["attn"], rms_norm(x, layer["ln1"], cfg.norm_eps),
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        causal=causal, window=window,
        impl=_select_impl(cfg, opts, s, window),
        q_chunk=opts.q_chunk, k_chunk=opts.k_chunk)
    return x + h.astype(x.dtype)


def ffn_or_moe_block(layer: Params, x: jax.Array, cfg: ModelConfig,
                     opts: RuntimeOptions) -> Tuple[jax.Array, jax.Array]:
    h = rms_norm(x, layer["ln2"], cfg.norm_eps)
    if cfg.arch_type == "moe":
        y, aux = moe_mod.moe_apply(layer["moe"], h, cfg,
                                   capacity_factor=opts.moe_capacity_factor)
    else:
        y = ffn_apply(layer["ffn"], h, gated=cfg.gated_ffn,
                      activation=cfg.activation,
                      hidden_shard_axis=opts.ffn_shard_axis)
        aux = jnp.zeros((), jnp.float32)
    return x + y.astype(x.dtype), aux


def transformer_block(layer: Params, x: jax.Array, cfg: ModelConfig,
                      opts: RuntimeOptions, *, window: int,
                      causal: bool = True,
                      cross_src: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    x = attn_block(layer, x, cfg, opts, window=window, causal=causal)
    if cross_src is not None and "cross" in layer:
        q = rms_norm(x, layer["ln_cross"], cfg.norm_eps)
        b, s, _ = q.shape
        hd = cfg.resolved_head_dim
        se = cross_src.shape[1]
        qh = (q @ layer["cross"]["wq"]).reshape(b, s, cfg.num_heads, hd)
        kh = (cross_src @ layer["cross"]["wk"]).reshape(b, se, cfg.num_kv_heads, hd)
        vh = (cross_src @ layer["cross"]["wv"]).reshape(b, se, cfg.num_kv_heads, hd)
        out = attn_mod.full_attention(qh, kh, vh, causal=False)
        x = x + (out.reshape(b, s, cfg.num_heads * hd)
                 @ layer["cross"]["wo"]).astype(x.dtype)
    return ffn_or_moe_block(layer, x, cfg, opts)


def mamba_block(layer: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    return x + ssm_mod.mamba_forward(
        layer["mamba"], rms_norm(x, layer["ln"], cfg.norm_eps), cfg).astype(x.dtype)


# -------------------------------------------------------------- the stack --
def _pattern_period(cfg: ModelConfig) -> Tuple[Tuple[str, ...], bool]:
    """Return (kinds of one period over *stacked* layers, shared_attn_after)."""
    if cfg.arch_type == "ssm":
        return (MAMBA,), False
    if cfg.arch_type == "hybrid":
        p = cfg.shared_attn_period or cfg.num_layers
        return tuple([MAMBA] * p), True
    if cfg.local_global_ratio:
        r = cfg.local_global_ratio
        return tuple([LOCAL] * r + [ATTN]), False
    return (ATTN,), False


def layer_window(cfg: ModelConfig, kind: str) -> int:
    """The attention window of a layer of ``kind`` (0 = global)."""
    return cfg.sliding_window if kind == LOCAL else 0


def walk_layers(cfg: ModelConfig, carry, stack, body: Callable, *,
                num_layers: Optional[int] = None,
                epilogue: Optional[Callable] = None, period_xs=None,
                wrap: Optional[Callable] = None):
    """Walk a stacked layer pytree in pattern periods (:func:`_pattern_period`).

    ``stack`` is any pytree whose leaves carry a leading per-layer axis:
    the layer weights and whatever the caller walks beside them (KV,
    SSM/conv state, pool planes, cross KV); ``None`` leaves pass through.
    ``body(carry, kind, layer_slice) -> (carry, out)`` runs one layer.
    ``epilogue(carry, period_slice) -> (carry, out)``, if given, runs after
    each whole period on that period's slice of ``period_xs`` (one entry
    per whole period): zamba2's shared attention.  ``wrap`` transforms the
    whole-period function (rematerialization).

    More than one whole period is ``lax.scan``-ed, the period unrolled
    inside, so compile time is O(1) in depth; a single one is unrolled.
    The layers that do not fill a period (zamba2's 38 % 6 == 2) run
    after, unrolled, through the same ``body``.  Only the first
    ``num_layers`` layers are walked when given (elastic depth).  Returns
    ``(carry, per-layer outs stacked in layer order, per-period epilogue
    outs)``."""
    kinds, _ = _pattern_period(cfg)
    period = len(kinds)
    total = jax.tree_util.tree_leaves(stack)[0].shape[0]
    n = total if num_layers is None else min(num_layers, total)
    groups = n // period
    n_full = groups * period

    def stacked(outs):
        return jax.tree_util.tree_map(lambda *o: jnp.stack(o), *outs)

    def run_period(carry, layers, px):
        outs = []
        for j, kind in enumerate(kinds):
            carry, o = body(carry, kind,
                            jax.tree_util.tree_map(lambda a: a[j], layers))
            outs.append(o)
        outs = stacked(outs)
        ep = None
        if epilogue is not None:
            carry, ep = epilogue(carry, px)
        return carry, (outs, ep)

    if wrap is not None:
        run_period = wrap(run_period)

    outs = eps = None
    if groups:
        grouped = jax.tree_util.tree_map(
            lambda a: a[:n_full].reshape(groups, period, *a.shape[1:]), stack)
        if groups > 1:
            carry, (outs, eps) = jax.lax.scan(
                lambda c, xs: run_period(c, *xs), carry, (grouped, period_xs))
            outs = jax.tree_util.tree_map(
                lambda a: a.reshape(n_full, *a.shape[2:]), outs)
        else:
            def first(tree):
                return jax.tree_util.tree_map(lambda a: a[0], tree)
            carry, (outs, eps) = run_period(carry, first(grouped),
                                            first(period_xs))
            eps = jax.tree_util.tree_map(lambda a: a[None], eps)
    tail = []
    for j in range(n_full, n):
        carry, o = body(carry, kinds[j - n_full],
                        jax.tree_util.tree_map(lambda a: a[j], stack))
        tail.append(o)
    if tail:
        tail = stacked(tail)
        outs = tail if outs is None else jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b]), outs, tail)
    return carry, outs, eps


def _maybe_seq_shard(x: jax.Array, opts: RuntimeOptions) -> jax.Array:
    """§Perf lever: constrain the residual stream to sequence-parallel
    sharding at block boundaries (Megatron-SP on the TPU mesh)."""
    if opts.seq_shard_axis and x.ndim == 3:
        from jax.sharding import PartitionSpec as P
        x = jax.lax.with_sharding_constraint(
            x, P(None, opts.seq_shard_axis, None))
    return x


def _remat_wrap(fn, opts: RuntimeOptions):
    if opts.remat == "none":
        return fn
    if opts.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def apply_stack(stack: Params, x: jax.Array, cfg: ModelConfig,
                opts: RuntimeOptions, *,
                shared: Optional[Params] = None,
                causal: bool = True,
                cross_src: Optional[jax.Array] = None,
                num_layers: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Run a stacked layer pytree over x.  Returns (x, aux_loss_sum).

    ``num_layers`` (static) < full depth realizes the elastic depth-scaling
    operator η5: only the first n layers' stacked weights are used.
    """
    _, shared_after = _pattern_period(cfg)

    def one_layer(carry, kind: str, layer: Params):
        x, aux = carry
        x = _maybe_seq_shard(x, opts)
        if kind == MAMBA:
            return (mamba_block(layer, x, cfg), aux), None
        x, a = transformer_block(layer, x, cfg, opts,
                                 window=layer_window(cfg, kind),
                                 causal=causal, cross_src=cross_src)
        return (x, aux + a), None

    def shared_block(carry, _):
        x, aux = carry
        x, a = transformer_block(shared, x, cfg, opts, window=0,
                                 causal=causal)
        return (x, aux + a), None

    (x, aux), _, _ = walk_layers(
        cfg, (x, jnp.zeros((), jnp.float32)), stack, one_layer,
        num_layers=num_layers,
        epilogue=shared_block if shared_after and shared is not None else None,
        wrap=functools.partial(_remat_wrap, opts=opts))
    return x, aux


# ------------------------------------------------------------- forward -----
def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            opts: RuntimeOptions = DEFAULT_OPTIONS, *,
            encoder_frames: Optional[jax.Array] = None,
            vision_embeds: Optional[jax.Array] = None,
            num_layers: Optional[int] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward (train / prefill).  Returns (logits, aux_loss).

    tokens: (B, S) int32.
    encoder_frames: (B, S_enc, D) stub audio embeddings (enc-dec archs).
    vision_embeds: (B, n_vis, vision_embed_dim) stub patch embeddings (VLM).
    """
    from .layers import cast_params
    act_dt = dtype_of(cfg.activation_dtype)
    params = cast_params(params, act_dt)
    x = embed_lookup(params["embed"], tokens).astype(act_dt)

    if cfg.vision_embed_dim and vision_embeds is not None:
        v = (vision_embeds.astype(act_dt) @ params["vision_proj"]["w"]
             + params["vision_proj"]["b"]).astype(act_dt)
        # vision embeddings occupy the first n_vis positions; the token ids
        # at those positions are placeholders (paper: modality frontend stub)
        x = jnp.concatenate([v, x[:, v.shape[1]:]], axis=1)

    cross_src = None
    if cfg.is_encoder_decoder and encoder_frames is not None:
        enc = encoder_frames.astype(act_dt)
        enc, _ = apply_stack(params["encoder"], enc, cfg,
                             opts.replace(attn_impl="full"), causal=False)
        cross_src = rms_norm(enc, params["encoder_norm"], cfg.norm_eps)

    x, aux = apply_stack(params["layers"], x, cfg, opts,
                         shared=params.get("shared_attn"),
                         cross_src=cross_src, num_layers=num_layers)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    if "logit_bias" in params:
        # TTA prior recalibration (paper §III-A2): a label-free-adaptable
        # output bias absorbing live unigram drift
        logits = logits + params["logit_bias"].astype(logits.dtype)
    logits = mask_padded_logits(logits, cfg)
    return logits, aux


def lm_loss(logits: jax.Array, labels: jax.Array,
            mask: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross entropy.  logits: (B,S,V); labels: (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def mask_padded_logits(logits: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Vocab rows beyond cfg.vocab_size are sharding padding — mask them."""
    return mask_padded_logits_raw(logits, cfg.vocab_size)
