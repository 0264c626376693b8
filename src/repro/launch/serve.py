"""Adaptive serving driver: batched requests through the ServingEngine
with the CrowdHMTware loop swapping variants as the context trace evolves.

  PYTHONPATH=src python -m repro.launch.serve --requests 24 --slots 4

``--decode-mode paged`` serves from the block pool through the paged
decode-attention step (the Pallas kernel on a TPU).  ``chip_smoke.py``
drives the same :func:`build` / :func:`serve` pair on the chip.
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, List, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.core import Budgets, Middleware, case_study_trace
from repro.models.configs import InputShape, ModelConfig
from repro.models.model import init_params
from repro.models.runtime import DEFAULT_OPTIONS
from repro.serving import CompileCache, Request, ServingEngine

from .cache import enable_compile_cache


def build(cfg: ModelConfig, *, slots: int, max_seq: int,
          decode_mode: str = "batched", seed: int = 0,
          compile_cache: CompileCache | None = None
          ) -> Tuple[ServingEngine, Middleware]:
    """An engine serving ``cfg`` with random weights from ``seed``, and
    the middleware that adapts it.  ``decode_mode="paged"`` binds the
    paged decode-attention step; the middleware's variant options are
    built over the engine's, so a swap keeps it."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    shape = InputShape("serve", max_seq, slots, "decode")
    opts = (DEFAULT_OPTIONS.replace(paged_kernel=True)
            if decode_mode == "paged" else DEFAULT_OPTIONS)
    mw = Middleware(cfg=cfg, params=params, shape=shape,
                    budgets=Budgets(latency_s=1.0, memory_bytes=8e9),
                    allow_offload=False, base_opts=opts)
    engine = ServingEngine(cfg, params, slots=slots, max_seq=max_seq,
                           decode_mode=decode_mode, opts=opts,
                           compile_cache=compile_cache)
    return engine, mw


def make_requests(vocab_size: int, n: int, *,
                  prompt_lens: Sequence[int] = (8, 48),
                  max_new_tokens: int = 12, seed: int = 0) -> List[Request]:
    """``n`` greedy requests whose prompt lengths are drawn uniformly
    from ``[prompt_lens[0], prompt_lens[1])``."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    return [Request(rid=i, prompt=rng.integers(
                        0, vocab_size, size=rng.integers(lo, hi)
                    ).astype(np.int32),
                    max_new_tokens=max_new_tokens)
            for i in range(n)]


def serve(engine: ServingEngine, mw: Middleware, requests: List[Request],
          *, adapt_every: int = 8,
          log: Callable[[str], None] = print) -> float:
    """Submit ``requests`` and step the engine until they are all served,
    letting the middleware adapt every ``adapt_every`` steps along the
    case-study context trace; a changed variant is swapped in mid-flight.
    Returns the wall seconds taken."""
    for r in requests:
        engine.submit(r)
    trace = list(case_study_trace(max(len(requests) // adapt_every, 2)))
    ti = 0
    t0 = time.time()
    step = 0
    while engine.has_work:
        engine.step()
        step += 1
        if step % adapt_every == 0 and ti < len(trace):
            d = mw.adapt(trace[ti])
            ti += 1
            vcfg, vparams, vopts = mw.current_runtime()
            if vcfg != engine.cfg or vopts != engine.opts:
                log(f"[adapt] {d.reason}: {d.action.describe()[:80]}")
                engine.swap_model(vcfg, vparams, vopts)
    return time.time() - t0


def main(argv: Sequence[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-backbone")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--adapt-every", type=int, default=8)
    ap.add_argument("--decode-mode", default="batched",
                    choices=["batched", "per_slot", "paged"])
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    engine, mw = build(cfg, slots=args.slots, max_seq=args.max_seq,
                       decode_mode=args.decode_mode)
    dt = serve(engine, mw, make_requests(cfg.vocab_size, args.requests),
               adapt_every=args.adapt_every)
    s = engine.stats
    print(f"served {args.requests} requests in {dt:.1f}s — "
          f"{s.steps} steps, {s.tokens_out} tokens "
          f"({s.tokens_per_step:.2f} tok/step), {s.prefills} prefills, "
          f"{s.recompiles} recompiles, {engine.generation} variant swaps")
    print(mw.report())


if __name__ == "__main__":
    main()
