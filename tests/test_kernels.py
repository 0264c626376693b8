"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (act_dequant, act_quant, flash_attention, fused_ffn,
                           ssd_scan)
from repro.kernels import ref


@pytest.mark.parametrize("m,n", [(128, 256), (256, 512), (64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_act_quant_matches_ref(m, n, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(m + n), (m, n)) * 3).astype(dtype)
    q, s = act_quant(x, interpret=True, block_m=64, block_n=128)
    qr, sr = ref.act_quant_ref(x)
    # identical up to +-1 level on round-half ties (f32 association order)
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert diff.max() <= 1
    assert (diff != 0).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    # roundtrip error bounded by scale/2 per element
    xd = act_dequant(q, s, out_dtype=jnp.float32, interpret=True,
                     block_m=64, block_n=128)
    err = jnp.abs(xd - x.astype(jnp.float32))
    bound = jnp.repeat(s, 128, axis=-1) * 0.51 + 1e-6
    assert bool(jnp.all(err <= bound))


@pytest.mark.parametrize("m,d,f", [(128, 64, 256), (256, 128, 512),
                                   (64, 96, 128)])
@pytest.mark.parametrize("activation", ["silu", "gelu"])
def test_fused_ffn_matches_ref(m, d, f, activation):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (m, d), jnp.float32) * 0.5
    wg = jax.random.normal(ks[1], (d, f)) * 0.1
    wu = jax.random.normal(ks[2], (d, f)) * 0.1
    wd = jax.random.normal(ks[3], (f, d)) * 0.1
    y = fused_ffn(x, wg, wu, wd, activation=activation, interpret=True,
                  block_m=64, block_f=128)
    yr = ref.fused_ffn_ref(x, wg, wu, wd, activation)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-5, rtol=1e-4)


def test_fused_ffn_bf16():
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = (jax.random.normal(ks[0], (128, 64)) * 0.5).astype(jnp.bfloat16)
    wg = (jax.random.normal(ks[1], (64, 256)) * 0.1).astype(jnp.bfloat16)
    wu = (jax.random.normal(ks[2], (64, 256)) * 0.1).astype(jnp.bfloat16)
    wd = (jax.random.normal(ks[3], (256, 64)) * 0.1).astype(jnp.bfloat16)
    y = fused_ffn(x, wg, wu, wd, interpret=True, block_m=64, block_f=128)
    yr = ref.fused_ffn_ref(x, wg, wu, wd, "silu")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=3e-2)


@pytest.mark.parametrize("s,hd", [(256, 64), (512, 128), (128, 32)])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_matches_ref(s, hd, window):
    ks = jax.random.split(jax.random.PRNGKey(s), 3)
    bh = 4
    q = jax.random.normal(ks[0], (bh, s, hd), jnp.float32)
    k = jax.random.normal(ks[1], (bh, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (bh, s, hd), jnp.float32)
    o = flash_attention(q, k, v, causal=True, window=window,
                        block_q=128, block_k=128, interpret=True)
    orf = ref.flash_attn_ref(q[None].reshape(1, bh, s, hd),
                             k.reshape(1, bh, s, hd),
                             v.reshape(1, bh, s, hd),
                             causal=True, window=window)[0]
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               atol=2e-5, rtol=1e-4)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = (jax.random.normal(kk, (2, 128, 64)) for kk in ks)
    o = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                        interpret=True)
    orf = ref.flash_attn_ref(q.reshape(1, 2, 128, 64),
                             k.reshape(1, 2, 128, 64),
                             v.reshape(1, 2, 128, 64), causal=False)[0]
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=2e-5)


@pytest.mark.parametrize("s,p,n,chunk", [(64, 16, 8, 16), (128, 32, 16, 32),
                                         (96, 8, 4, 32)])
def test_ssd_scan_matches_ref(s, p, n, chunk):
    bh = 3
    ks = jax.random.split(jax.random.PRNGKey(s + p), 5)
    x = jax.random.normal(ks[0], (bh, s, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, s)))
    a = -jnp.exp(jax.random.normal(ks[2], (bh,)) * 0.2)
    b = jax.random.normal(ks[3], (bh, s, n)) * 0.5
    c = jax.random.normal(ks[4], (bh, s, n)) * 0.5
    y, st = ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=True)
    yr, str_ = ref.ssd_scan_kernel_ref(x, dt, a, b, c, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_),
                               atol=1e-4, rtol=1e-3)


def test_ssd_scan_chunk_invariance():
    """The kernel result must not depend on the chunk size."""
    bh, s, p, n = 2, 128, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (bh, s, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, s)))
    a = -jnp.exp(jax.random.normal(ks[2], (bh,)) * 0.2)
    b = jax.random.normal(ks[3], (bh, s, n)) * 0.5
    c = jax.random.normal(ks[4], (bh, s, n)) * 0.5
    y16, st16 = ssd_scan(x, dt, a, b, c, chunk=16, interpret=True)
    y64, st64 = ssd_scan(x, dt, a, b, c, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st16), np.asarray(st64),
                               atol=1e-4, rtol=1e-3)


def test_ops_dispatch_cpu_fallback():
    from repro.kernels import ops
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    q1, s1 = ops.quantize_activations(x, use_pallas=False)
    q2, s2 = ops.quantize_activations(x, use_pallas=True, interpret=True)
    assert int(jnp.abs(q1.astype(jnp.int32) - q2.astype(jnp.int32)).max()) <= 1


@pytest.mark.parametrize("m,n", [(64, 256), (128, 512)])
def test_act_quant4_matches_engine_codec(m, n):
    from repro.engine import quantize_int4
    from repro.kernels import act_quant4
    x = jax.random.normal(jax.random.PRNGKey(m * n), (m, n)) * 2
    packed, s = act_quant4(x, interpret=True, block_m=64, block_n=128)
    ref_packed, ref_s = quantize_int4(x)
    # engine codec blocks over the flattened last dim identically
    diff = np.asarray(packed, np.int32) - np.asarray(ref_packed, np.int32)
    # allow rare +-1-level tie differences in EITHER nibble
    lo = np.abs((diff & 0xF).astype(np.int8))
    assert (np.minimum(lo, 16 - lo) <= 1).all()
    np.testing.assert_allclose(np.asarray(s),
                               np.asarray(ref_s.reshape(s.shape)), rtol=1e-5)


# ------------------------------------------------ fully-masked-row guard --
def test_flash_kv_len_zero_outputs_exactly_zero():
    """Regression: a fully-masked query row used to finalize to the
    uniform average of its (masked) keys — ``m_new == NEG_INF`` makes
    ``exp(s - m_new) == exp(0) == 1`` for every key.  With the guard the
    row is exactly zero, in kernel and oracle alike."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 32)) for kk in ks)
    o = flash_attention(q, k, v, kv_len=0, block_q=32, block_k=32,
                        interpret=True)
    assert bool(jnp.all(o == 0.0))
    orf = ref.flash_attn_ref(q[:, None], k[:, None], v[:, None], kv_len=0)
    assert bool(jnp.all(orf == 0.0))


def test_flash_window_beyond_kv_len_rows_are_zero():
    """window=1 + kv_len: row i's only candidate key is column i, which
    is masked for i >= kv_len — those rows must be exactly zero while
    earlier rows still attend themselves (softmax over one key == v)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 32)) for kk in ks)
    kv_len = 24
    o = flash_attention(q, k, v, window=1, kv_len=kv_len,
                        block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o[:, :kv_len]),
                               np.asarray(v[:, :kv_len]), atol=2e-6)
    assert bool(jnp.all(o[:, kv_len:] == 0.0))
    orf = ref.flash_attn_ref(q[:, None], k[:, None], v[:, None],
                             window=1, kv_len=kv_len)[:, 0]
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=2e-6)


def test_flash_kv_len_matches_truncated_cache():
    """kv_len masking must equal physically truncating the KV to
    kv_len for every row that still has valid keys."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 32)) for kk in ks)
    kv_len = 32
    o = flash_attention(q, k, v, kv_len=kv_len, block_q=32, block_k=32,
                        interpret=True)
    # rows < kv_len see the identical causal prefix
    o_trunc = flash_attention(q[:, :kv_len], k[:, :kv_len], v[:, :kv_len],
                              block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o[:, :kv_len]),
                               np.asarray(o_trunc), atol=2e-5, rtol=1e-4)


# ---------------------------------------------------- sliding-window edges --
def test_window_one_attends_self_only():
    """window=1, causal: the valid set (i-1, i] is exactly {i}, so every
    output row is its own value row (softmax over one key)."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 32)) for kk in ks)
    o = flash_attention(q, k, v, window=1, block_q=32, block_k=32,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(v), atol=2e-6)


def test_window_geq_seq_equals_plain_causal():
    """A window that covers the whole sequence is a no-op."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 32)) for kk in ks)
    o_w = flash_attention(q, k, v, window=64, block_q=32, block_k=32,
                          interpret=True)
    o_c = flash_attention(q, k, v, window=0, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(o_w), np.asarray(o_c), atol=1e-6)
    o_big = flash_attention(q, k, v, window=1000, block_q=32, block_k=32,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(o_big), np.asarray(o_c), atol=1e-6)


def test_noncausal_window_semantics():
    """causal=False + window=w keeps only the *lower* bound: row i
    attends every key in (i-w, S) — lookback is clipped, lookahead is
    unlimited.  Pinned against an explicit dense computation."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    s, hd, w = 64, 32, 8
    q, k, v = (jax.random.normal(kk, (2, s, hd)) for kk in ks)
    o = flash_attention(q, k, v, causal=False, window=w,
                        block_q=32, block_k=32, interpret=True)
    scores = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(hd)
    mask = jnp.arange(s)[None, :] > jnp.arange(s)[:, None] - w
    dense = jnp.einsum(
        "bqk,bkd->bqd",
        jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1), v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(dense),
                               atol=2e-5, rtol=1e-4)
    orf = ref.flash_attn_ref(q[:, None], k[:, None], v[:, None],
                             causal=False, window=w)[:, 0]
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               atol=2e-5, rtol=1e-4)


# ------------------------------------------------------------- int4 codec --
@pytest.mark.parametrize("m,n", [(64, 256), (128, 512)])
def test_act_dequant4_matches_ref(m, n):
    from repro.kernels import act_dequant4, act_quant4
    x = jax.random.normal(jax.random.PRNGKey(m + n), (m, n)) * 2
    packed, s = act_quant4(x, interpret=True, block_m=64, block_n=128)
    d_kernel = act_dequant4(packed, s, out_dtype=jnp.float32,
                            interpret=True, block_m=64, block_n=128)
    d_ref = ref.act_dequant4_ref(packed, s, dtype=jnp.float32)
    # same packed bytes + same scales -> dequant is exact, not approx
    np.testing.assert_array_equal(np.asarray(d_kernel), np.asarray(d_ref))


def test_act_quant4_roundtrip_is_exact_on_codes():
    """pack -> unpack -> repack is the identity on the packed bytes: the
    dequantized tensor re-quantizes to the same codes AND the same
    scales (scale = amax/7 survives because the per-block amax is itself
    a code-7 point, exactly representable)."""
    x = jax.random.normal(jax.random.PRNGKey(11), (64, 256)) * 3
    p1, s1 = ref.act_quant4_ref(x)
    d1 = ref.act_dequant4_ref(p1, s1, dtype=jnp.float32)
    p2, s2 = ref.act_quant4_ref(d1)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_act_quant4_range_is_symmetric():
    """The code space is the symmetric [-7, 7]: biased nibbles live in
    [1, 15] and nibble 0 (code -8) never occurs, so negating the input
    negates the codes exactly."""
    x = jax.random.normal(jax.random.PRNGKey(12), (32, 256)) * 4
    packed, _ = ref.act_quant4_ref(x)
    lo = np.asarray(packed & 0xF, np.int32)
    hi = np.asarray(packed >> 4, np.int32)
    assert lo.min() >= 1 and hi.min() >= 1          # -8 deliberately unused
    neg_packed, _ = ref.act_quant4_ref(-x)
    nlo = np.asarray(neg_packed & 0xF, np.int32) - 8
    nhi = np.asarray(neg_packed >> 4, np.int32) - 8
    np.testing.assert_array_equal(nlo, -(lo - 8))
    np.testing.assert_array_equal(nhi, -(hi - 8))


# ------------------------------------------------------ paged decode attn --
def _paged_case(seed, slots, H, kvh, hd, bs, mb, kv_dtype, pos_spec):
    """Build one paged-decode problem; pos_spec picks the ragged lengths."""
    from repro.kernels.act_quant import kv_quant_rows
    rng = np.random.default_rng(seed)
    nb = mb * slots + 2
    q = jnp.asarray(rng.standard_normal((slots, H, hd)), jnp.float32)
    kb = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)), jnp.float32)
    vb = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, nb, (slots, mb)), jnp.int32)
    kn = jnp.asarray(rng.standard_normal((slots, kvh, hd)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((slots, kvh, hd)), jnp.float32)
    if pos_spec == "ragged":
        pos = jnp.asarray(rng.integers(0, mb * bs + 1, (slots,)), jnp.int32)
    elif pos_spec == "zero":
        pos = jnp.zeros((slots,), jnp.int32)
    elif pos_spec == "full_tail":           # every tail block just filled
        pos = jnp.full((slots,), mb * bs, jnp.int32)
    kwargs = {}
    if kv_dtype == "int8":
        kb, ks = kv_quant_rows(kb)
        vb, vs = kv_quant_rows(vb)
        kwargs = dict(k_scale=ks, v_scale=vs)
    elif kv_dtype == "bfloat16":
        kb, vb = kb.astype(jnp.bfloat16), vb.astype(jnp.bfloat16)
    return (q, kb, vb, tables, pos, kn, vn), kwargs


# (bs, mb): one grid step; then several steps of R blocks — R = 8
# (Yi-34B's 144-block sweep has 18 such steps), R = 7, and R = 1 where mb
# (11) has no divisor near 128 / bs
@pytest.mark.parametrize("bs,mb", [(4, 5), (8, 3), (16, 2), (16, 16),
                                   (16, 14), (16, 11)])
@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("pos_spec", ["ragged", "zero", "full_tail"])
def test_paged_decode_matches_ref(bs, mb, kv_dtype, pos_spec):
    from repro.kernels import paged_decode_attention
    args, kw = _paged_case(bs * mb, slots=3, H=4, kvh=2, hd=16,
                           bs=bs, mb=mb, kv_dtype=kv_dtype,
                           pos_spec=pos_spec)
    o_k = paged_decode_attention(*args, interpret=True, **kw)
    o_r = ref.paged_decode_attn_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("window", [1, 3, 100])
def test_paged_decode_window_matches_ref(window):
    from repro.kernels import paged_decode_attention
    args, _ = _paged_case(17, slots=4, H=8, kvh=4, hd=16, bs=4, mb=4,
                          kv_dtype="float32", pos_spec="ragged")
    o_k = paged_decode_attention(*args, window=window, interpret=True)
    o_r = ref.paged_decode_attn_ref(*args, window=window)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("mb,bs,per_step", [
    (144, 16, 8), (128, 16, 8), (16, 16, 8), (14, 16, 7), (7, 16, 7),
    (11, 16, 1), (3, 64, 1), (3, 4, 3), (48, 8, 16), (64, 4, 32),
    (2, 256, 1)])
def test_paged_decode_blocks_per_step(mb, bs, per_step):
    """R is the largest divisor of mb with R * bs <= 128, else 1."""
    from repro.kernels.paged_decode_attn import blocks_per_step
    assert blocks_per_step(mb, bs) == per_step


def _dead_blocks_nan_case(bs, mb, kv_dtype, window):
    """One slot per boundary position, each over blocks of its own, with
    the trash block and every whole block outside the slot's live rows
    ``[lo, pos)`` filled with NaN (K, V and int8 scales).  Returns the
    poisoned and the clean problem."""
    from repro.kernels.act_quant import kv_quant_rows
    from repro.kernels.paged_decode_attn import blocks_per_step
    rows = blocks_per_step(mb, bs) * bs
    pos = sorted({0, bs - 1, bs, bs + 1, rows - 1, rows, rows + 1,
                  mb * bs - 1} & set(range(mb * bs)))
    slots, kvh, hd, group = len(pos), 2, 16, 2
    rng = np.random.default_rng(bs * mb + window)
    nb = slots * mb + 1                      # block 0 is the trash block
    kb = rng.standard_normal((nb, bs, kvh, hd)).astype(np.float32)
    vb = rng.standard_normal((nb, bs, kvh, hd)).astype(np.float32)
    tables = 1 + np.arange(slots * mb, dtype=np.int32).reshape(slots, mb)
    dead = [0]
    for s, p in enumerate(pos):
        lo = max(p - window + 1, 0) if window else 0
        for c in range(mb):
            if c * bs >= p or (c + 1) * bs <= lo:   # no live row in it
                dead.append(tables[s, c])
                if s % 2:                   # as the engine: the trash block
                    tables[s, c] = 0
    args = [jnp.asarray(rng.standard_normal((slots, kvh * group, hd)),
                        jnp.float32)]
    tail = [jnp.asarray(tables), jnp.asarray(pos, jnp.int32),
            jnp.asarray(rng.standard_normal((slots, kvh, hd)), jnp.float32),
            jnp.asarray(rng.standard_normal((slots, kvh, hd)), jnp.float32)]
    kw = {}
    if kv_dtype == "int8":
        kq, ks = map(np.asarray, kv_quant_rows(jnp.asarray(kb)))
        vq, vs = map(np.asarray, kv_quant_rows(jnp.asarray(vb)))
        clean = (args + [jnp.asarray(kq), jnp.asarray(vq)] + tail,
                 dict(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs)))
        ks, vs = ks.copy(), vs.copy()
        ks[dead] = vs[dead] = np.nan
        kw = dict(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
        return (clean[0], kw), clean
    clean = (args + [jnp.asarray(kb), jnp.asarray(vb)] + tail, kw)
    kb, vb = kb.copy(), vb.copy()
    kb[dead] = vb[dead] = np.nan
    return (args + [jnp.asarray(kb), jnp.asarray(vb)] + tail, kw), clean


@pytest.mark.parametrize("bs,mb", [(16, 16), (8, 48), (16, 14), (16, 11),
                                   (4, 3)])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
@pytest.mark.parametrize("window", [0, 40])
def test_paged_decode_never_reads_dead_blocks(bs, mb, kv_dtype, window):
    """A block that holds no live row is neither fetched into the sweep
    nor masked in it: NaN there (and in the trash block) leaves every
    output finite and equal to the oracle on the clean pool.  Positions
    sit at 0, around a block boundary, around a step boundary of R * bs
    rows and at the last row; with a window the leading blocks are dead
    too."""
    from repro.kernels import paged_decode_attention
    (args, kw), (clean, clean_kw) = _dead_blocks_nan_case(bs, mb, kv_dtype,
                                                          window)
    o_k = np.asarray(paged_decode_attention(*args, window=window,
                                            interpret=True, **kw))
    o_r = np.asarray(ref.paged_decode_attn_ref(*clean, window=window,
                                               **clean_kw))
    assert np.isfinite(o_k).all()
    np.testing.assert_allclose(o_k, o_r, atol=2e-5, rtol=1e-4)


def test_paged_decode_pos_zero_is_new_token_only():
    """A brand-new slot's pool sweep is fully masked; the only valid key
    is the just-computed token, so out == v_new per kv head (regression
    for the masked-row guard in the decode kernel)."""
    from repro.kernels import paged_decode_attention
    args, _ = _paged_case(23, slots=2, H=4, kvh=2, hd=16, bs=4, mb=3,
                          kv_dtype="float32", pos_spec="zero")
    q, kb, vb, tables, pos, kn, vn = args
    o = paged_decode_attention(*args, interpret=True)
    expect = jnp.repeat(vn, 2, axis=1)          # group=2 heads per kv head
    np.testing.assert_allclose(np.asarray(o), np.asarray(expect), atol=2e-6)


def test_paged_decode_matches_dense_decode():
    """The block-table kernel against the dense one-token attention it
    replaces: lay the same KV out densely (new token scattered at pos)
    and paged (new token folded in), outputs must agree."""
    from repro.kernels import paged_decode_attention
    from repro.models.attention import decode_attention
    rng = np.random.default_rng(41)
    slots, H, kvh, hd, bs, mb = 3, 4, 2, 16, 4, 4
    s_len = mb * bs
    nb = slots * mb + 1
    q = jnp.asarray(rng.standard_normal((slots, H, hd)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((slots, s_len, kvh, hd)), jnp.float32)
    vd = jnp.asarray(rng.standard_normal((slots, s_len, kvh, hd)), jnp.float32)
    pos = jnp.asarray([0, 7, 15], jnp.int32)
    # paged layout: slot s owns blocks [1 + s*mb, 1 + (s+1)*mb)
    tables = jnp.asarray(
        [[1 + s * mb + j for j in range(mb)] for s in range(slots)],
        jnp.int32)
    kb = jnp.zeros((nb, bs, kvh, hd), jnp.float32)
    vb = jnp.zeros((nb, bs, kvh, hd), jnp.float32)
    kb = kb.at[tables.reshape(-1)].set(
        kd.reshape(slots * mb, bs, kvh, hd))
    vb = vb.at[tables.reshape(-1)].set(
        vd.reshape(slots * mb, bs, kvh, hd))
    # the dense path sees the new token *scattered at pos*; the kernel
    # folds the same rows in as k_new/v_new
    kn = jnp.stack([kd[s, pos[s]] for s in range(slots)])
    vn = jnp.stack([vd[s, pos[s]] for s in range(slots)])
    for w in (0, 3):
        o_p = paged_decode_attention(q, kb, vb, tables, pos, kn, vn,
                                     window=w, interpret=True)
        o_d = jnp.stack([
            decode_attention(q[s:s + 1], kd[s:s + 1], vd[s:s + 1],
                             pos[s], window=w)[0]
            for s in range(slots)])
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_d),
                                   atol=2e-5, rtol=1e-4)


def test_paged_decode_int8_error_bound():
    """int8 KV attention stays within the quantization error envelope of
    the f32 pool (per-row scales: relative error ~1/254 per element)."""
    from repro.kernels import paged_decode_attention
    from repro.kernels.act_quant import kv_quant_rows
    args, _ = _paged_case(29, slots=4, H=8, kvh=2, hd=32, bs=8, mb=3,
                          kv_dtype="float32", pos_spec="ragged")
    q, kb, vb, tables, pos, kn, vn = args
    o_f32 = paged_decode_attention(*args, interpret=True)
    kq, ks = kv_quant_rows(kb)
    vq, vs = kv_quant_rows(vb)
    o_i8 = paged_decode_attention(q, kq, vq, tables, pos, kn, vn,
                                  k_scale=ks, v_scale=vs, interpret=True)
    assert float(jnp.max(jnp.abs(o_i8 - o_f32))) < 0.05


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(seed=st.integers(0, 2**16), kvh=st.sampled_from([1, 2, 4]),
           group=st.sampled_from([1, 2, 3]), bs=st.sampled_from([4, 8, 16]),
           mb=st.integers(1, 4), kv_dtype=st.sampled_from(
               ["float32", "bfloat16", "int8"]),
           pos_spec=st.sampled_from(["ragged", "zero", "full_tail"]),
           window=st.sampled_from([0, 1, 5]))
    def test_paged_decode_matches_ref_fuzzed(seed, kvh, group, bs, mb,
                                             kv_dtype, pos_spec, window):
        from repro.kernels import paged_decode_attention
        args, kw = _paged_case(seed, slots=2, H=kvh * group, kvh=kvh,
                               hd=16, bs=bs, mb=mb, kv_dtype=kv_dtype,
                               pos_spec=pos_spec)
        o_k = paged_decode_attention(*args, window=window, interpret=True,
                                     **kw)
        o_r = ref.paged_decode_attn_ref(*args, window=window, **kw)
        np.testing.assert_allclose(np.asarray(o_k, np.float32),
                                   np.asarray(o_r, np.float32),
                                   atol=3e-5, rtol=2e-4)
