"""One run of one cell: set-up, the measured window, the output check.

The cell names a configuration (``configs/<name>.json``: the model's sizes
and the limits of the output check) and a traffic mix
(``traffic/<name>.json``: lengths, clients, engine sizing).  The harness
builds the weights from the seed, serves the mix through the program's
``ServingEngine`` on the paged decode path (``decode_mode="paged"``,
``paged_kernel=True``, a bf16 pool), and stamps every token on the host
when ``step()`` returns.

Set-up warms every program the mix can reach with a throwaway engine (each
prompt bucket times each burst size), so nothing compiles in the window,
then admits every client's first request, so the window opens on a full
engine; the harness counts compiles and cache loads in the window and
reports them.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

CHIP = Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
CACHE_DIR = ROOT / ".jax_cache"

# the output check: requests drawn for it, and the served tokens it aims at
CHECK_TOKENS = 512
CHECK_MAX_REQUESTS = 8
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(SystemExit):
    """Raised when the devices JAX finds cannot run the cell."""


# ------------------------------------------------------------- loading --
def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(bench: dict, workload: str):
    """``(cell, configuration dict, mix dict)`` of a workload."""
    sys.path.insert(0, str(CHIP / "traffic"))
    import generator
    cell = find(bench["workloads"], workload, "workload")
    conf = find(bench["configs"], cell["config"], "configuration")
    config = json.loads((ROOT / conf["file"]).read_text())
    return cell, config, generator.load_mix(cell["traffic"])


def import_program():
    """The system under test, from the checkout's ``src``."""
    sys.path.insert(0, str(ROOT / "src"))
    import repro  # noqa: F401  (fails here when the program is absent)


def enable_cache() -> None:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache``,
    every program kept and none evicted (a size cap set in the
    environment would evict the warm-up's programs in turn, so that no
    run finds them), so only a checkout's first run compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"benchmark: needs a TPU, found platform {d.platform!r}"
                     f" ({d.device_kind}, {len(devs)} devices)")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"benchmark: the cell needs {chips} chips, found "
                     f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def load_peak(kind: str, require: bool = True) -> Optional[dict]:
    table = json.loads((CHIP / "peaks.json").read_text())["devices"]
    if kind not in table:
        if require:
            raise KeyError(f"device kind {kind!r} is not in peaks.json")
        return None
    return table[kind]


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, as module
    ``<kind>_<name>`` (a family and a reference may share a name)."""
    import importlib.util
    mod_name = f"{kind}_{name}"
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            mod_name, CHIP / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[mod_name]


def family(config: dict):
    """The configuration's family module (``families/<name>.py``): the
    program's configuration object, weights and FLOP counts."""
    return load_module("families", config["family"])


# --------------------------------------------------------- bookkeeping --
@dataclass
class Track:
    spec: object
    req: object
    stamps: List[float] = field(default_factory=list)

    @property
    def tokens(self) -> List[int]:
        return self.req.generated

    @property
    def finished(self) -> bool:
        return self.req.done and len(self.stamps) == len(self.tokens)


@dataclass
class Step:
    t0: float
    t1: float
    decode_s: float
    emitted: int
    blocks_used: int
    prefill_tokens: int = 0        # real prompt tokens admitted this step
    flops: float = 0.0             # model FLOPs of the tokens it produced
    attn_flops: float = 0.0        # needed work of the paged attention
    attn_bytes: float = 0.0


class Compiles:
    """Counts programs built (compiled or loaded from the persistent
    cache) while ``active``."""

    def __init__(self):
        self.active = False
        self.compiled = 0
        self.loaded = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if self.active and event == COMPILE_EVENT:
            self.compiled += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if self.active and event == CACHE_HIT_EVENT:
            self.loaded += 1

    def reset(self):
        self.compiled = self.loaded = 0
        self.compile_s = 0.0


# --------------------------------------------------------------- a run --
class Run:
    """Everything one run measured; the metric readers read this.

    ``control=True`` puts the check's control in the program's place: the
    reference with every matmul in int8 picks the tokens it compares."""

    def __init__(self, cell, config, mix, seed, seconds, trace,
                 require_tpu=True, control=False):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.trace_on = seed, float(seconds), trace
        self.require_tpu = require_tpu
        self.control = control
        self.eng = mix["engine"]
        self.max_burst = mix.get("max_burst", self.eng["slots"])
        self.steps: List[Step] = []
        self.tracks: List[Track] = []
        self.trace = None
        self.setup_s = None
        self.live: List[Track] = []        # submitted and not finished
        self._queues = None                # each client's next requests

    # ............................................................ set-up
    def setup(self, t_process: float) -> None:
        from repro.models.runtime import DEFAULT_OPTIONS
        sys.path.insert(0, str(CHIP))
        sys.path.insert(0, str(CHIP / "traffic"))
        import generator
        self.fam = family(self.config)
        self.device = check_devices(self.cell["chips"], self.require_tpu)
        self.peak = load_peak(self.device["kind"], self.require_tpu)
        self.cfg = self.fam.model_config(self.config)
        self.layers = self.cfg.num_layers
        self.opts = DEFAULT_OPTIONS.replace(
            paged_kernel=True, kv_cache_dtype=self.config["compute_dtype"])
        self.compiles = Compiles()
        self.compiles.active = True
        self.params = self.fam.make_params(self.config, self.seed)
        self.specs = generator.generate(self.mix, self.seed,
                                        self.config["vocab_size"])
        self.buckets = generator.buckets_of(self.mix)
        self._warm()
        self.engine = self._engine()
        self.setup_compiles = (self.compiles.compiled, self.compiles.loaded,
                               self.compiles.compile_s)
        self.compiles.reset()
        # every client's first request admitted, at most a burst a step
        while len(self.live) < self.mix["clients"]:
            self._closed_refill()
            self._step()
        jax.block_until_ready(self.params)
        gc.collect()
        self.setup_s = time.perf_counter() - t_process

    def _engine(self):
        from repro.serving import ServingEngine
        return ServingEngine(self.cfg, self.params, slots=self.eng["slots"],
                             max_seq=self.eng["max_seq"], opts=self.opts,
                             decode_mode="paged",
                             block_size=self.eng["block_size"])

    def _warm(self) -> None:
        """Run every program the mix can reach once, on a throwaway engine:
        each prompt bucket at each burst size up to ``max_burst``, and a
        decode step."""
        from repro.serving import Request
        ks = [1 << i for i in range(int(math.log2(self.max_burst)) + 1)]
        rng = np.random.default_rng(0)
        vocab = self.config["vocab_size"]
        rid = iter(range(10 ** 9, 2 * 10 ** 9))
        eng = self._engine()
        for b in self.buckets:
            for k in ks:
                for _ in range(k):
                    eng.submit(Request(rid=next(rid), prompt=rng.integers(
                        1, vocab, size=b).astype(np.int32), max_new_tokens=2))
                eng.step()
                eng.drain()
        del eng
        gc.collect()
        jax.block_until_ready(self.params)

    # ........................................................... window
    def _submit(self, spec) -> None:
        from repro.serving import Request
        req = Request(rid=spec.rid, prompt=spec.prompt.copy(),
                      max_new_tokens=spec.max_new_tokens,
                      arrived_s=time.perf_counter())
        tr = Track(spec=spec, req=req)
        self.tracks.append(tr)
        self.live.append(tr)
        self.engine.submit(req)

    def _closed_refill(self) -> None:
        """Every client without a request in flight sends its next one,
        at most ``max_burst`` of them before the next step."""
        if self._queues is None:
            self._queues = {}
            for spec in self.specs:
                self._queues.setdefault(spec.client, []).append(spec)
        busy = {tr.spec.client for tr in self.live if not tr.finished}
        sent = 0
        for c, queue in self._queues.items():
            if sent == self.max_burst:
                break
            if c not in busy and queue:
                self._submit(queue.pop(0))
                sent += 1

    def _step(self) -> None:
        fam = self.fam
        eng = self.engine
        t0 = time.perf_counter()
        with _annotate("bench.step", self.trace_on):
            emitted = eng.step()
        t1 = time.perf_counter()
        st = Step(t0, t1, eng.step_times[-1], emitted,
                  eng.block_pool.used_blocks)
        model = self.config
        still = []
        for tr in self.live:
            n, seen = len(tr.tokens), len(tr.stamps)
            ctx = len(tr.spec.prompt)
            for t in range(seen, n):
                tr.stamps.append(t1)
                if t == 0:
                    st.prefill_tokens += ctx
                    st.flops += fam.prompt_flops(model, self.layers, ctx)
                else:
                    cached = _bucket(ctx, self.eng["max_seq"]) + t - 1
                    st.flops += fam.token_flops(model, self.layers, ctx + t)
                    f, b = fam.paged_attn_work(model, self.layers, cached)
                    st.attn_flops += f
                    st.attn_bytes += b
            if not tr.finished:
                still.append(tr)
        self.live = still
        self.steps.append(st)

    def window(self) -> None:
        if self.trace_on:
            self._trace_dir = ROOT / ".bench_trace"
            import shutil
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self._trace_dir),
                                     profiler_options=opts)
        self.compiles.active = True
        t0 = time.perf_counter()
        self.t0, self.t_end = t0, t0 + self.seconds
        with _annotate("bench.window", self.trace_on):
            while time.perf_counter() < self.t_end:
                self._closed_refill()
                if not self.engine.has_work:
                    break                  # every client has sent its all
                self._step()
        self.compiles.active = False
        self.submitted = len(self.tracks)
        if self.trace_on:
            jax.profiler.stop_trace()

    def finish_trace(self) -> None:
        import xplane
        self.trace = xplane.load(self._trace_dir)

    def memory_peak(self) -> int:
        peaks = []
        for d in jax.devices()[:max(1, self.cell["chips"])]:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)

    def free_engine(self) -> None:
        self.engine = None
        gc.collect()
        jax.block_until_ready(self.params)

    # ............................................................ check
    def check(self) -> Dict[str, dict]:
        """Compare what the window served with the plain reference: a
        sample drawn from the seed, the longest request in it, each
        recomputed by the reference over its context and served tokens.
        Returns the numbers compared, each with its limit: the gaps the
        configuration's ``correct`` gives a limit for (the widest,
        ``max_gap``, and the mean over the tokens, ``mean_gap``), and two
        exact counts."""
        ref = load_module("references", self.config["reference"])
        done = [t for t in self.tracks if t.tokens]
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 0xC4EC])
        sample = []
        if done:
            longest = max(done, key=lambda t: len(t.spec.prompt)
                          + len(t.tokens))
            sample.append(longest)
            rest = [t for t in done if t is not longest]
            for j in rng.permutation(len(rest)):
                if sum(len(t.tokens) for t in sample) >= CHECK_TOKENS or \
                        len(sample) >= CHECK_MAX_REQUESTS:
                    break
                sample.append(rest[j])
        max_seq = self.eng["max_seq"]
        gap, n_tok = 0.0, 0
        all_gaps = []
        t0 = time.perf_counter()
        for tr in sample:
            ctx = tr.spec.prompt[-max_seq:]
            # the context as the engine left-pads it, padding attended
            padded = np.zeros(_bucket(len(ctx), max_seq), np.int32)
            padded[len(padded) - len(ctx):] = ctx
            g = ref.served_gaps(self.params, self.config, self.layers,
                                padded, list(tr.tokens),
                                control=self.control)
            gap = max(gap, float(np.max(g)))
            n_tok += len(tr.tokens)
            all_gaps.append(g)
        self.budget_misses = 0
        for tr in self.tracks:
            if tr.req.done and len(tr.tokens) != tr.spec.max_new_tokens:
                pos = _bucket(len(tr.spec.prompt), max_seq) \
                    + len(tr.tokens) - 1
                if len(tr.tokens) > tr.spec.max_new_tokens \
                        or pos < max_seq - 1:
                    self.budget_misses += 1
        self.check_s = time.perf_counter() - t0
        g = np.concatenate(all_gaps) if all_gaps else np.zeros(0)
        self.check_requests, self.check_tokens = len(sample), n_tok
        self.gaps = g
        limits = self.config["correct"]
        out = {name: {"value": value, "limit": limits.get(name),
                      "op": "<="}
               for name, value in (("max_gap", gap),
                                   ("mean_gap", float(g.mean()) if len(g)
                                    else float("nan")))
               if name in limits}
        out["tokens_checked"] = {"value": n_tok, "limit": 1, "op": ">="}
        out["budget_misses"] = {"value": self.budget_misses, "limit": 0,
                                "op": "<="}
        return out


def correct_from(check: Dict[str, dict]) -> bool:
    """Every number within its limit (a number that is not finite is
    not)."""
    for c in check.values():
        v = c["value"]
        if c["limit"] is None or not math.isfinite(v):
            return False
        if not (v <= c["limit"] if c["op"] == "<=" else v >= c["limit"]):
            return False
    return True


def _bucket(n: int, max_seq: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return min(b, max_seq)


def _annotate(name: str, on: bool):
    """A span in the profiler's trace (``bench.*``), where tracing is on."""
    if not on:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)

