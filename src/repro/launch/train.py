"""End-to-end training driver.

Runs real steps on whatever devices exist (CPU debug mesh by default; the
production mesh when chips are available), with the middleware adaptation
loop optionally in control of remat/sub-batching as memory budgets change.

Example (the examples/train_e2e.py driver uses this):
  PYTHONPATH=src python -m repro.launch.train --arch paper-backbone \
      --steps 200 --batch 8 --seq 256 --d-model 512 --layers 12
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config
from repro.data import SyntheticLM, DataConfig
from repro.models.configs import InputShape, ModelConfig
from repro.models.model import init_params
from repro.optim import adamw

from .cache import enable_compile_cache
from .mesh import make_debug_mesh
from .steps import make_train_step, options_for


def train_loop(cfg: ModelConfig, shape: InputShape, steps: int,
               seed: int = 0, log_every: int = 10,
               remat: str = "none",
               checkpoint_dir: Optional[str] = None,
               callback=None) -> dict:
    opts = options_for(cfg, shape, {"remat": remat})
    key = jax.random.PRNGKey(seed)
    params = init_params(cfg, key)
    opt_state = adamw.init(params)
    step_fn = jax.jit(make_train_step(cfg, opts), donate_argnums=(0, 1))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=shape.seq_len,
                                  batch_size=shape.global_batch, seed=seed))
    losses = []
    t0 = time.time()
    for i in range(steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if i % log_every == 0 or i == steps - 1:
            loss = float(metrics["loss"])
            losses.append((i, loss))
            print(f"step {i:5d}  loss {loss:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"{(time.time() - t0) / (i + 1):.2f}s/step", flush=True)
        if callback is not None:
            params, opt_state = callback(i, params, opt_state, metrics)
    if checkpoint_dir:
        save_checkpoint(f"{checkpoint_dir}/step_{steps:06d}", params,
                        step=steps, metadata={"arch": cfg.name})
    return {"losses": losses, "params": params,
            "seconds": time.time() - t0}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-backbone")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--checkpoint-dir", default="")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    kw = {}
    if args.layers:
        kw["num_layers"] = args.layers
    if args.d_model:
        kw["d_model"] = args.d_model
        kw["head_dim"] = 0
    if kw:
        cfg = cfg.with_updates(**kw)
    shape = InputShape("cli", args.seq, args.batch, "train")
    n = cfg.param_count()
    print(f"training {cfg.name}: {n/1e6:.1f}M params, "
          f"{args.steps} steps @ batch={args.batch} seq={args.seq}")
    out = train_loop(cfg, shape, args.steps, remat=args.remat,
                     checkpoint_dir=args.checkpoint_dir or None)
    first, last = out["losses"][0][1], out["losses"][-1][1]
    print(f"loss {first:.3f} -> {last:.3f} in {out['seconds']:.0f}s")


if __name__ == "__main__":
    main()
