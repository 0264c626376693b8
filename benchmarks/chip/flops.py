"""Operations and bytes of the model's work, computed from shapes.

The benchmark's own count, kept apart from the program: a step's FLOPs
for the whole-step utilization (``step_mfu_pct``), and the paged decode
attention's needed work for its roofline share.  ``model`` is a
configuration file's dict (Hugging Face key names).
"""
from __future__ import annotations


def _dims(model: dict):
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    kvh = model["num_key_value_heads"]
    hd = model.get("head_dim") or d // h
    return d, h, kvh, hd, model["intermediate_size"], model["vocab_size"]


def dense_flops_per_token(model: dict, layers: int) -> float:
    """Matmul FLOPs of one token through ``layers`` layers and the tied
    LM head: q, k, v, o, the gated FFN's three matrices, and the
    unembedding.  Attention over the context is :func:`attn_flops`."""
    d, h, kvh, hd, f, v = _dims(model)
    per_layer = d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f
    return 2.0 * (layers * per_layer + d * v)


def attn_flops(model: dict, layers: int, ctx: int) -> float:
    """Score and value FLOPs of one query over ``ctx`` keys per layer:
    ``4 * heads * head_dim * ctx``."""
    _, h, _, hd, _, _ = _dims(model)
    return 4.0 * layers * h * hd * ctx


def token_flops(model: dict, layers: int, ctx: int) -> float:
    """Model FLOPs of one token that attends over ``ctx`` keys."""
    return dense_flops_per_token(model, layers) + attn_flops(model, layers,
                                                             ctx)


def prompt_flops(model: dict, layers: int, n: int) -> float:
    """Model FLOPs of prefilling ``n`` tokens causally (token ``i`` sees
    ``i + 1`` keys)."""
    _, h, _, hd, _, _ = _dims(model)
    causal_keys = n * (n + 1) / 2
    return (n * dense_flops_per_token(model, layers)
            + 4.0 * layers * h * hd * causal_keys)


def paged_attn_work(model: dict, layers: int, pos: int,
                    kv_bytes: int = 2, act_bytes: int = 2):
    """Needed work of the paged decode attention for one slot at ``pos``
    cached rows, over ``layers`` layers: ``(flops, bytes)``.

    FLOPs: ``4 * heads * head_dim`` per key, over the ``pos`` cached keys
    and the new one.  Bytes: each cached K and V row read once, the new
    K and V rows, q read and the output written.  Blocks past ``pos`` are
    not needed work, whatever the kernel sweeps."""
    _, h, kvh, hd, _, _ = _dims(model)
    keys = pos + 1
    flops = 4.0 * h * hd * keys
    nbytes = (2 * pos * kvh * hd * kv_bytes      # cached K and V rows
              + 2 * kvh * hd * act_bytes         # the new K and V rows
              + 2 * h * hd * act_bytes)          # q in, output out
    return layers * flops, layers * float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip needs for the work: the larger of FLOPs at
    peak bf16 rate and bytes at peak HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
