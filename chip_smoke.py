"""Bring-up smoke of the adaptive serving path on one TPU chip.

  python chip_smoke.py

One process, one line per phase, at the full registered width of
``paper-backbone`` with random weights from a fixed seed:

* ``device`` — fails at once unless JAX's first device is a TPU.
* ``kernel`` — the paged decode-attention op the served path calls, on a
  bf16 pool and an int8 pool, against the f32 ``ref.py`` oracle; its
  program must hold the Pallas kernel (``tpu_custom_call``).
* ``serve``  — :mod:`repro.launch.serve`'s loop: paged decode through
  the kernel step, the middleware swapping variants mid-flight.  Every
  request gets its whole budget, and the paged decode program holds the
  kernel before and after the swap.
* ``parity`` — the same greedy requests through the kernel engine and a
  dense ``decode_mode="batched"`` engine: first decode step's logits
  within tolerance, and the share of tokens that agree.

Any failed check raises, so the script exits non-zero without its last
line.  That line is one JSON object: ``{"ok": true, "device": {...}}``.
The compile cache is ``$JAX_COMPILATION_CACHE_DIR``, else
``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.act_quant import kv_quant_rows  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build, make_requests, serve  # noqa: E402
from repro.models.configs import ModelConfig  # noqa: E402
from repro.models.model import (decode_step, init_paged_pool,  # noqa: E402
                                init_paged_slot_cache, init_params,
                                paged_kernel_decode_logits)
from repro.models.runtime import DEFAULT_OPTIONS  # noqa: E402
from repro.serving import CompileCache, ServingEngine  # noqa: E402
from repro.serving.paging import TRASH_BLOCK  # noqa: E402

# what a compiled program holds when the Pallas kernel is in it
KERNEL_MARK = "tpu_custom_call"
# kernel vs the f32 oracle, per slot as a share of the slot's largest
# output (slot_rel_err): bf16 q/KV/output rounding plus MXU accumulation
# order.  Dropping one block of a full slot misses by >= 0.05 at SEED
# (tests/test_chip_smoke.py plants it)
KERNEL_RTOL = 2e-2
# first-step logits, kernel engine vs dense engine, as a share of the
# largest dense logit: two bf16 attention implementations over 8 layers
LOGIT_RTOL = 5e-2
SEED = 0

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _require(ok: bool, msg: str) -> None:
    """A failed check: raise, whatever ``python -O`` does to asserts."""
    if not ok:
        raise RuntimeError(msg)


def check_device() -> dict:
    """The device JAX found, as the last line reports it; exits at once
    unless it is a TPU."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{d.platform!r} ({d.device_kind}, {len(devs)} "
                         "devices)")
    print(f"device: {d.device_kind} x{len(devs)}, jax {jax.__version__}",
          flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def kernel_cases(cfg: ModelConfig, *, slots: int = 8, max_seq: int = 2048,
                 block_size: int = 16, layers: int = 3) -> dict:
    """Random operands of ``ops.paged_attention`` at ``cfg``'s attention
    geometry: slots ragged from empty to full (pos ``0 .. max_seq``), each
    over blocks of its own, in a block-major pool of ``layers`` layers of
    different KV, read at the last (the layers before it are drawn after
    every other operand, so the layer read does not depend on their
    count).  ``{"bf16": args, "int8": args}``, one per pool dtype,
    ``args`` in the op's order."""
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    mb = max_seq // block_size
    nb = slots * mb + 1
    rng = np.random.default_rng(SEED)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q = normal(slots, h, hd).astype(jnp.bfloat16)
    plane = (nb, 1, block_size, kvh, hd)
    kf, vf = normal(*plane), normal(*plane)
    kn = normal(slots, kvh, hd).astype(jnp.bfloat16)
    vn = normal(slots, kvh, hd).astype(jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(nb - 1).reshape(slots, mb),
                         jnp.int32)
    before = (nb, layers - 1) + plane[2:]
    kf = jnp.concatenate([normal(*before), kf], axis=1)
    vf = jnp.concatenate([normal(*before), vf], axis=1)
    pos = jnp.asarray(np.linspace(0, max_seq, slots), jnp.int32)
    kq, ks = kv_quant_rows(kf)
    vq, vs = kv_quant_rows(vf)
    layer = jnp.int32(layers - 1)
    return {"bf16": (q, kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16),
                     tables, pos, kn, vn, layer, None, None),
            "int8": (q, kq, vq, tables, pos, kn, vn, layer, ks, vs)}


def oracle(args) -> np.ndarray:
    """The f32 ``ref.py`` answer for ``ops.paged_attention``'s operands."""
    q, kb, vb, tables, pos, kn, vn, layer, ksc, vsc = args
    f32 = jnp.float32
    with jax.default_matmul_precision("float32"):
        return np.asarray(ref.paged_decode_attn_ref(
            q.astype(f32), kb, vb, tables, pos, kn.astype(f32),
            vn.astype(f32), layer, k_scale=ksc, v_scale=vsc))


def slot_rel_err(out: np.ndarray, want: np.ndarray) -> float:
    """Worst over slots of ``max|out - want| / max|want|`` in the slot:
    a slot over thousands of keys averages its values down to ~0.1 while
    one at pos 0 returns ``v_new`` (~3), so one absolute limit would
    either fail the empty slot or pass a full one missing blocks."""
    n = len(want)
    err = np.abs(out - want).reshape(n, -1).max(axis=1)
    return float(np.max(err / np.abs(want).reshape(n, -1).max(axis=1)))


def kernel_phase(cfg: ModelConfig, **geometry) -> None:
    """``ops.paged_attention`` on :func:`kernel_cases`' bf16 and int8
    pools vs the f32 oracle; its program must hold the kernel."""
    parts = []
    for name, args in kernel_cases(cfg, **geometry).items():
        compiled = ops.paged_attention.lower(*args).compile()
        _require(KERNEL_MARK in compiled.as_text(),
                 f"{name} pool: paged attention program holds no kernel")
        out = np.asarray(compiled(*args), np.float32)
        want = oracle(args)
        _require(np.isfinite(out).all(), f"{name} pool: non-finite output")
        rel = slot_rel_err(out, want)
        _require(rel <= KERNEL_RTOL, f"{name} pool: kernel vs oracle, worst "
                 f"slot max|err|/max|oracle| {rel} > {KERNEL_RTOL}")
        parts.append(f"{name} max|err| {np.max(np.abs(out - want)):.3g}, "
                     f"worst slot rel {rel:.3g}")
    q, _, _, tables, *_ = args
    print(f"kernel: slots={q.shape[0]} heads={cfg.num_heads} "
          f"kv_heads={cfg.num_kv_heads} head_dim={q.shape[2]} "
          f"blocks/slot={tables.shape[1]}; {'; '.join(parts)} "
          f"(tol {KERNEL_RTOL}); {KERNEL_MARK} in program", flush=True)


def _decode_has_kernel(engine: ServingEngine) -> bool:
    return KERNEL_MARK in engine.lower_decode().compile().as_text()


def _steady_decode_s(engine: ServingEngine, steps: int, *,
                     full: bool) -> float:
    """Median seconds of the engine's bound paged decode program, each
    call closed by ``block_until_ready``.  ``full``: every slot holds
    ``max_seq - steps - 1`` tokens in blocks of its own, so the kernel
    sweeps and fetches every block of every slot (the served worst
    case).  Otherwise every slot is empty and every table entry is the
    trash block: the kernel sweeps no block and fetches none."""
    compiled = engine.lower_decode().compile()
    cache = init_paged_slot_cache(engine.cfg, engine.slots, engine.max_seq,
                                  engine.opts)
    pool = init_paged_pool(engine.cfg, engine.pool_blocks,
                           engine.block_size, engine.opts)
    mb = engine.max_seq // engine.block_size
    tables = np.full((engine.slots, mb), TRASH_BLOCK, np.int32)
    if full:
        cache["pos"] = jnp.full_like(cache["pos"], engine.max_seq - steps - 1)
        ids = np.random.default_rng(SEED).permutation(
            np.arange(1, engine.pool_blocks))
        tables = ids[:engine.slots * mb].reshape(engine.slots, mb)
    tokens = jnp.zeros(engine.slots, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    out = jax.block_until_ready(
        compiled(engine.params, cache, pool, tokens, tables))
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            compiled(engine.params, out[2], out[3], tokens, tables))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def serve_phase(cfg: ModelConfig, *, slots: int = 8, max_seq: int = 2048,
                requests: int = 16, prompt_lens=(8, 513),
                max_new_tokens: int = 24, adapt_every: int = 8,
                timed_steps: int = 20) -> None:
    """The served path end to end: paged kernel decode under the
    middleware's variant swaps.  ``max_seq`` leaves room for a swap's
    re-prefill of prompt + generated tokens, whose bucket can double."""
    engine, mw = build(cfg, slots=slots, max_seq=max_seq,
                       decode_mode="paged", seed=SEED,
                       compile_cache=CompileCache())
    _require(_decode_has_kernel(engine),
             "decode program before the swap holds no kernel")
    reqs = make_requests(cfg.vocab_size, requests, prompt_lens=prompt_lens,
                         max_new_tokens=max_new_tokens, seed=SEED)
    compiles = []

    def on_event(event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        wall = serve(engine, mw, reqs, adapt_every=adapt_every,
                     log=lambda s: print(f"serve: {s}", flush=True))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    want = requests * max_new_tokens
    served = sum(len(r.generated) for r in reqs)
    _require(all(r.done for r in reqs), "requests left unfinished")
    _require(served == want and engine.stats.tokens_out == want,
             f"served {served} tokens ({engine.stats.tokens_out} counted), "
             f"want {requests} x {max_new_tokens} = {want}")
    _require(engine.generation >= 1, "the middleware swapped no variant")
    _require(engine.opts.paged_kernel, "the swap dropped the kernel step")
    _require(_decode_has_kernel(engine),
             "decode program after the swap holds no kernel")
    full_s = _steady_decode_s(engine, timed_steps, full=True)
    empty_s = _steady_decode_s(engine, timed_steps, full=False)
    print(f"serve: {cfg.name} d_model={cfg.d_model} layers={cfg.num_layers} "
          f"slots={slots} max_seq={max_seq}; {requests} requests, "
          f"{served} tokens = {requests} x {max_new_tokens}; "
          f"{engine.generation} variant swaps, {KERNEL_MARK} in decode "
          f"program before and after; wall {wall:.2f} s, compile "
          f"{sum(compiles):.2f} s over {len(compiles)} programs, "
          f"engine recompiles {engine.stats.recompiles}; steady decode "
          f"of the swapped-in variant ({engine.cfg.num_layers} layers, "
          f"median of {timed_steps}, block_until_ready) "
          f"{full_s * 1e3:.3f} ms/step with every slot full, "
          f"{empty_s * 1e3:.3f} ms/step on an empty pool", flush=True)


def _first_step_logits(engine: ServingEngine) -> dict:
    """Admit the queue, then return ``{rid: logits}`` of the decode step
    the engine would take next, without taking it."""
    engine._admit()
    active = [(s, r) for s, r in enumerate(engine._active) if r is not None]
    tokens = np.zeros(engine.slots, np.int32)
    for s, r in active:
        tokens[s] = r.generated[-1]
    tokens = jnp.asarray(tokens)
    if engine.decode_mode == "paged":
        step = jax.jit(paged_kernel_decode_logits, static_argnums=(1, 6))
        logits = step(engine.params, engine.cfg, engine._cache,
                      engine._pool, tokens,
                      jnp.asarray(engine.block_pool.tables), engine.opts)[0]
    else:
        cfg, opts = engine.cfg, engine.opts
        logits = jax.jit(jax.vmap(
            lambda p, c, t: decode_step(p, cfg, c, t[None], opts)[0][0],
            in_axes=(None, 0, 0)))(engine.params, engine._cache, tokens)
    logits = np.asarray(logits[:, :engine.cfg.vocab_size], np.float32)
    return {r.rid: logits[s] for s, r in active}


def parity_phase(cfg: ModelConfig, *, slots: int = 8, max_seq: int = 1024,
                 requests: int = 16, prompt_lens=(8, 513),
                 max_new_tokens: int = 24) -> None:
    """Kernel engine vs dense batched engine on the same greedy requests,
    same weights, no adaptation."""
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    cc = CompileCache()
    streams, first = {}, {}
    for mode, opts in (("paged", DEFAULT_OPTIONS.replace(paged_kernel=True)),
                       ("batched", DEFAULT_OPTIONS)):
        engine = ServingEngine(cfg, params, slots=slots, max_seq=max_seq,
                               decode_mode=mode, opts=opts, compile_cache=cc)
        reqs = make_requests(cfg.vocab_size, requests,
                             prompt_lens=prompt_lens,
                             max_new_tokens=max_new_tokens, seed=SEED)
        for r in reqs:
            engine.submit(r)
        first[mode] = _first_step_logits(engine)
        engine.drain()
        _require(all(r.done for r in reqs),
                 f"{mode}: requests left unfinished")
        streams[mode] = [r.generated for r in reqs]
    _require(first["paged"].keys() == first["batched"].keys(),
             "the two engines admitted different requests first")
    got = np.stack([first["paged"][i] for i in sorted(first["paged"])])
    want = np.stack([first["batched"][i] for i in sorted(first["paged"])])
    _require(np.isfinite(got).all(), "kernel engine: non-finite logits")
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    _require(rel <= LOGIT_RTOL,
             f"first-step logits: max|Δ|/max|dense| {rel} > {LOGIT_RTOL}")
    same = sum(int(a == b) for sa, sb in zip(streams["paged"],
                                             streams["batched"])
               for a, b in zip(sa, sb))
    total = sum(len(s) for s in streams["batched"])
    print(f"parity: first decode step over {len(got)} slots, "
          f"max|Δlogit|/max|dense logit| {rel:.3g} (tol {LOGIT_RTOL}); "
          f"token agreement {same / total:.4f} ({same}/{total})", flush=True)


def main() -> None:
    device = check_device()
    cache = Path(enable_compile_cache())
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"cache: {cache}, {warm} entries at start", flush=True)
    cfg = get_config("paper-backbone")
    kernel_phase(cfg)
    # a lane-aligned head dim: the kernel reads each block out of the
    # whole pool (paper-backbone's 32 lanes read a padded copy of a plane)
    kernel_phase(cfg.with_updates(head_dim=128))
    serve_phase(cfg)
    parity_phase(cfg)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
