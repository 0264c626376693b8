"""CrowdHMTware middleware facade (paper §III-D3).

The paper's public surface is ``run.py(device_id, model, IP, PORT, fuse,
quan)``; the TPU-framework analogue keeps the same spirit: register a
model once, then let the middleware own variant selection, placement and
engine configuration while the application just calls ``infer`` /
``train_step``.  "It hides run-time system issues from developers."
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.elastic.supernet import ElasticSupernet
from repro.elastic.tta import tta_step
from repro.models.configs import InputShape, ModelConfig, TRAIN_4K
from repro.models.layers import Params
from repro.models.model import decode_step, forward, init_cache, prefill
from repro.models.runtime import DEFAULT_OPTIONS, RuntimeOptions

from .loop import AdaptationLoop, Decision
from .monitor import ResourceContext
from .optimizer import Budgets
from .profiler import HardwareProfile, TPU_V5E


@dataclass
class Middleware:
    """run(device_id, model, ...) → adaptive execution."""
    cfg: ModelConfig
    params: Params
    shape: InputShape = TRAIN_4K
    hw: HardwareProfile = TPU_V5E
    budgets: Budgets = field(default_factory=Budgets)
    fuse: bool = True
    quan: bool = False              # the paper API's activation-quant flag
    tta_enabled: bool = True
    allow_offload: bool = True
    # what a variant's engine config is applied over: options it does not
    # govern (a paged engine's kernel and pool dtype) come from here
    base_opts: RuntimeOptions = DEFAULT_OPTIONS

    def __post_init__(self):
        self.supernet = ElasticSupernet(self.cfg, self.params)
        self.loop = AdaptationLoop(cfg=self.cfg, shape=self.shape,
                                   supernet=self.supernet, hw=self.hw,
                                   budgets=self.budgets,
                                   allow_offload=self.allow_offload)
        self.loop.build_pareto(evolve=False)
        self._compiled: Dict[Any, Callable] = {}
        self._drift_seen = 0.0

    # ------------------------------------------------------------ control --
    def adapt(self, ctx: ResourceContext) -> Decision:
        """One loop tick: monitor -> profile -> optimize -> reconfigure."""
        d = self.loop.tick(ctx)
        if self.tta_enabled and ctx.data_drift - self._drift_seen > 0.25:
            self._drift_seen = ctx.data_drift
        return d

    def current_runtime(self) -> Tuple[ModelConfig, Params, RuntimeOptions]:
        if self.loop.current is None:
            self.adapt(ResourceContext())
        return self.loop.materialize(self.base_opts)

    # ------------------------------------------------------------ serving --
    def infer(self, tokens: jax.Array, **fwd_kw) -> jax.Array:
        vcfg, vparams, opts = self.current_runtime()
        key = (vcfg.name, vcfg.num_layers, vcfg.d_ff, vcfg.num_kv_heads,
               opts, "fwd")
        if key not in self._compiled:
            self._compiled[key] = jax.jit(
                lambda p, t, kw: forward(p, vcfg, t, opts, **kw)[0],
                static_argnames=())
        return self._compiled[key](vparams, tokens, fwd_kw)

    def adapt_weights(self, live_tokens: jax.Array, lr: float = 1e-3
                      ) -> float:
        """Test-time adaptation on unlabeled live data (drift mitigation)."""
        vcfg, vparams, opts = self.current_runtime()
        new_params, ent = tta_step(self.supernet.backbone_params, self.cfg,
                                   live_tokens, lr=lr)
        self.supernet.backbone_params = new_params
        self.supernet._cache.clear()       # variants re-derive lazily
        self._drift_seen = 0.0
        return float(ent)

    def report(self) -> str:
        lines = ["tick  reason                      action"]
        for d in self.loop.decisions[-10:]:
            lines.append(f"{d.tick:4d}  {d.reason:26s} {d.action.describe()}")
        return "\n".join(lines)
