"""Batched serving runtime: request scheduler + uniform-step decode engine.

Requests arrive asynchronously; the scheduler packs them into fixed decode
slots (continuous batching with slot recycling).  Under the middleware, the
adaptation loop may swap the model variant or engine options between
decode steps — the engine re-jits lazily and keeps per-slot caches valid
only within a variant generation (the paper's "per-second adaptation
frequency" maps to a generation counter here).

Two decode paths share the scheduler:

* ``decode_mode="batched"`` (default) — ONE slot-stacked cache pytree of
  shape ``(slots, ...)`` and one jitted decode step per tick.  Per-slot
  sampling (temperature / top-k / PRNG key, living as leaves of the
  stacked cache) happens on device; slots with temperature 0 argmax
  exactly as the historical greedy engine did.  The tick does a single
  bulk device→host transfer of ``(slots,)`` tokens + positions, and the
  stacked cache is *donated* to the step so KV/SSM buffers update in
  place.  Inactive slots are masked (their outputs ignored), never
  skipped — the decode shape is constant, so one compiled program serves
  every occupancy.
* ``decode_mode="per_slot"`` — the original reference loop: one jit call
  and one host sync per active slot.  Kept for equivalence tests and as
  the benchmark baseline; token streams are bit-identical across modes.
* ``decode_mode="paged"`` — the slot-stacked step, but self-attention
  KV lives in a :class:`~repro.serving.paging.BlockPool` of fixed-size
  blocks instead of a dense ``max_seq`` row per slot.  Host-side block
  tables ride into the jitted step as runtime data (constant shape —
  occupancy, sharing and admission churn never recompile), prompt
  blocks are deduplicated by prefix chain hash (same-system-prompt
  admissions share prefill blocks, copy-on-write), and a full-prompt
  prefix cache re-admits an already-seen padded prompt without any
  prefill jit call.  Token streams are bit-identical to ``"batched"``.
  Two runtime options specialize this path (both live in
  ``RuntimeOptions``, hence in every CompileCache key and freeze/thaw
  fingerprint): ``paged_kernel=True`` decodes through the Pallas
  block-table attention kernel — attention reads KV straight from pool
  blocks, no gather-to-dense detour — and ``kv_dtype="int8"`` stores
  the pool int8 with per-row scales (~4x resident slots per device;
  greedy streams match the f32 pool on the differential corpus).
  A hybrid MoE stack (``arch_type="hybrid_moe"``, Mamba-2 and attention
  mixers) serves on this path only: its attention K/V live in the pool
  and its Mamba layers' recurrent state in per-slot leaves beside it,
  written by admission, carried by freeze/thaw and swaps, and updated in
  place by the decode step.  The blocks cannot rebuild that state, so a
  prefix-cache hit on such a stack is refused and prefilled
  (``engine.prefix_refused_recurrent`` counts it).

Any non-``per_slot`` engine can **freeze** an in-flight request into a
host-side :class:`~repro.serving.paging.FrozenRequest` blob (pages
densified + trimmed to ``pos``, sampling subtree, consumed count) and
**thaw** it later — on itself or on a fleet peer whose ``(cfg, opts,
params_version)`` fingerprint matches — with zero token loss and zero
re-prefill.  ``requeue_active`` and ``swap_model`` route through
freeze/thaw, so a same-weights swap no longer re-prefills; a
fingerprint mismatch falls back to the legacy requeue-with-re-prefill.

Admission is batched too (``prefill_mode="batched"``, the default on the
batched decode path): ``_admit`` drains every waiting request that shares
the head-of-line request's prompt bucket — the head is never skipped, so
a stream of same-bucket arrivals cannot starve an earlier waiter from
another bucket — and runs ONE ``(k, bucket)`` prefill jit call whose
results are scattered straight into their slots on device.  Burst sizes
are bucketed (powers of two capped at the slot count, short bursts padded
with throwaway rows), so mixed burst sizes reuse a handful of programs.
``prefill_mode="per_request"`` keeps the sequential reference admission
(one prefill jit per request), which the property suite pins the batched
path against.  While slots decode, batched admission is *staged*: each
prefill call stalls every decoding slot's next token, so a tick prefills
one cohort — the requests the last admission passed over, all of them,
or else the head's bucket among newer arrivals, whose other buckets wait
one tick.  No request waits twice, and no burst mixes the two cohorts,
so none is larger than what arrived between two admissions.

Compiled programs come from a :class:`CompileCache` shared across engines
(process-global by default), so a fleet of same-platform engines compiles
each program once — ``ServeStats.recompiles`` counts only the programs
*this* engine's requests actually caused to be built.  Sampling options
never enter the cache key (they are runtime arrays), so engines with
heterogeneous per-slot policies still share every program.
"""
from __future__ import annotations

import contextlib
import itertools
import time
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Callable, Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.act_quant import kv_dequant_rows
from repro.models.configs import ModelConfig
from repro.models.hybrid_moe import STATE_LEAVES
from repro.models.layers import Params, dtype_of
from repro.models.model import (SAMPLER_BRANCHES, init_cache,
                                init_paged_pool, init_paged_slot_cache,
                                init_slot_cache)
from repro.models.runtime import DEFAULT_OPTIONS, RuntimeOptions
from repro.obs import NULL_RECORDER, MetricsRegistry, counting

from .compile_cache import GLOBAL_COMPILE_CACHE, CompileCache, ServePrograms
from .paging import (DEFAULT_BLOCK_SIZE, TRASH_BLOCK, BlockPool,
                     FrozenRequest, PrefixCache, PrefixEntry,
                     block_hash_chain, blocks_needed)
from .sampling import (DEFAULT_SAMPLING, SamplingOpts, request_key,
                       sampler_branch)

DECODE_MODES = ("batched", "per_slot", "paged")
PREFILL_MODES = ("batched", "per_request")

# cache leaves whose sequence axis (axis 2 in batch=1 layout) is trimmed
# to ``pos`` when freezing — everything past pos is zero by construction
_SEQ_TRIM_LEAVES = ("k", "v", "shared_k", "shared_v")

# default observability pids: distinct per engine so two untagged
# engines sharing one TraceRecorder never interleave on one track
_ENGINE_SEQ = itertools.count()


@dataclass
class Request:
    """One generation request in the serving queue.  ``rid`` is the
    caller's identifier (echoed back, never interpreted — but folded into
    the request's PRNG key, so reuse rids deliberately); ``prompt`` is
    the int32 token array to prefill; ``max_new_tokens`` bounds the
    generated continuation (the prefill's first sampled token counts
    toward it).  ``sampling`` overrides the engine's default
    :class:`SamplingOpts` for this request (``None`` inherits it).  The
    engine fills the remaining fields: ``generated`` accumulates sampled
    tokens, ``done`` flips when the budget or ``max_seq`` is reached, and
    the ``*_s`` stamps record queue/latency milestones on the caller's
    clock (``arrived_s`` is stamped at :meth:`ServingEngine.submit` when
    the caller leaves it 0, ``first_token_s`` when the prefill's token
    lands on the host)."""
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    arrived_s: float = 0.0
    sampling: Optional[SamplingOpts] = None
    # filled by the engine
    generated: List[int] = field(default_factory=list)
    done: bool = False
    first_token_s: Optional[float] = None
    finished_s: Optional[float] = None
    # set when the request carries serialized in-flight state (a requeue,
    # preemption or migration); a compatible engine thaws it with zero
    # re-prefill, an incompatible one falls back to re-prefilling
    # prompt+generated (the legacy requeue contract)
    frozen: Optional[FrozenRequest] = None


class ServeStats:
    """Counters for one engine's lifetime: decode ``steps`` taken,
    ``tokens_out`` emitted (prefill + decode), ``prefills`` — *requests*
    prefilled — and ``prefill_calls`` — prefill *jit invocations*; a
    burst of k same-bucket admissions is k prefills but 1 prefill call.
    ``sampled_tokens`` counts tokens drawn stochastically (from requests
    whose effective :class:`SamplingOpts` temperature is > 0; the rest
    are greedy).  ``recompiles`` is the number of jitted programs *this*
    engine's requests caused to be built (0 on an engine that found
    everything in a warm :class:`CompileCache`, which is how fleet-wide
    program sharing is asserted).  ``backend_compiles`` counts XLA's own
    compiles inside this engine's ticks (:mod:`repro.obs.compiles`),
    eager ops and loads from the persistent compilation cache included.

    Since the observability layer landed this is a **view** over the
    engine's :class:`~repro.obs.metrics.MetricsRegistry` — each
    attribute reads/writes the like-named ``engine.*`` counter, so the
    historical ``eng.stats.steps`` surface and the registry can never
    disagree.  A standalone ``ServeStats()`` owns a private registry."""

    _COUNTERS = {"steps": "engine.steps",
                 "tokens_out": "engine.tokens_out",
                 "prefills": "engine.prefills",
                 "prefill_calls": "engine.prefill_calls",
                 "sampled_tokens": "engine.sampled_tokens",
                 "recompiles": "engine.recompiles",
                 "backend_compiles": "engine.backend_compiles",
                 "oom_events": "engine.oom_events",
                 "requeues": "engine.requeues",
                 "freezes": "engine.freezes",
                 "thaws": "engine.thaws"}

    def __init__(self, metrics: Optional[MetricsRegistry] = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        for name in self._COUNTERS.values():
            self.metrics.counter(name)

    def _get(self, attr: str) -> int:
        return self.metrics.counter(self._COUNTERS[attr]).value

    def _set(self, attr: str, v: int) -> None:
        self.metrics.counter(self._COUNTERS[attr]).value = v

    steps = property(lambda s: s._get("steps"),
                     lambda s, v: s._set("steps", v))
    tokens_out = property(lambda s: s._get("tokens_out"),
                          lambda s, v: s._set("tokens_out", v))
    prefills = property(lambda s: s._get("prefills"),
                        lambda s, v: s._set("prefills", v))
    prefill_calls = property(lambda s: s._get("prefill_calls"),
                             lambda s, v: s._set("prefill_calls", v))
    sampled_tokens = property(lambda s: s._get("sampled_tokens"),
                              lambda s, v: s._set("sampled_tokens", v))
    recompiles = property(lambda s: s._get("recompiles"),
                          lambda s, v: s._set("recompiles", v))
    backend_compiles = property(lambda s: s._get("backend_compiles"),
                                lambda s, v: s._set("backend_compiles", v))
    oom_events = property(lambda s: s._get("oom_events"),
                          lambda s, v: s._set("oom_events", v))
    requeues = property(lambda s: s._get("requeues"),
                        lambda s, v: s._set("requeues", v))
    freezes = property(lambda s: s._get("freezes"),
                       lambda s, v: s._set("freezes", v))
    thaws = property(lambda s: s._get("thaws"),
                     lambda s, v: s._set("thaws", v))

    @property
    def tokens_per_step(self) -> float:
        return self.tokens_out / max(self.steps, 1)

    def __repr__(self) -> str:
        fields = ", ".join(f"{a}={self._get(a)}" for a in self._COUNTERS)
        return f"ServeStats({fields})"


class ServingEngine:
    """Slot-based continuous batching over the unified decode API.

    ``slots`` fixes the decode batch width (requests beyond it queue);
    ``max_seq`` bounds prompt+generation length per slot.
    ``decode_mode`` selects the decode path: ``"batched"`` (default)
    advances every slot in one vmapped, cache-donating jit call with
    on-device per-slot sampling and a single bulk transfer per tick,
    while ``"per_slot"`` is the reference loop — one jit call and host
    sync per active slot — kept for equivalence tests and benchmarking
    (token streams are bit-identical across modes).  ``prefill_mode``
    selects the admission path: ``"batched"`` (default under batched
    decode) packs same-bucket waiting requests into one burst prefill
    call; ``"per_request"`` is the sequential reference (and the only
    path under ``decode_mode="per_slot"``, which has no stacked cache to
    scatter into).  ``sampling`` is the default :class:`SamplingOpts`
    for requests that don't carry their own — the zero default is greedy,
    bit-identical to the pre-sampling engine.  ``compile_cache`` /
    ``compile_domain`` wire the engine into cross-engine program
    sharing: programs are keyed on ``(cfg, opts, slots, max_seq,
    domain)``, and ``compile_domain`` namespaces the key by compile
    target (platform/ISA) since a pixel_6 cannot reuse a jetson's
    binaries — the fleet controller passes each device's
    :attr:`DeviceSpec.compile_domain` here."""

    def __init__(self, cfg: ModelConfig, params: Params, *, slots: int = 8,
                 max_seq: int = 512, opts: RuntimeOptions = DEFAULT_OPTIONS,
                 decode_mode: str = "batched",
                 prefill_mode: str = "batched",
                 sampling: SamplingOpts = DEFAULT_SAMPLING,
                 compile_cache: Optional[CompileCache] = None,
                 compile_domain: str = "",
                 recorder=NULL_RECORDER,
                 pid: Optional[str] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 block_size: int = DEFAULT_BLOCK_SIZE,
                 pool_blocks: Optional[int] = None,
                 prefix_entries: int = 32,
                 params_version: Optional[int] = None):
        if decode_mode not in DECODE_MODES:
            raise ValueError(f"unknown decode_mode {decode_mode!r}; "
                             f"expected one of {DECODE_MODES}")
        if prefill_mode not in PREFILL_MODES:
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}; "
                             f"expected one of {PREFILL_MODES}")
        if decode_mode == "paged":
            # every prompt bucket (powers of two from 16, capped at
            # max_seq) must be block-aligned so prompts fill whole blocks
            # and decode always writes a private tail block
            if block_size < 1 or block_size & (block_size - 1) \
                    or block_size > 16:
                raise ValueError(f"block_size {block_size} must be a "
                                 "power of two <= 16")
            if max_seq % block_size:
                raise ValueError(f"block_size {block_size} must divide "
                                 f"max_seq {max_seq}")
            per_slot_blocks = max_seq // block_size
            if pool_blocks is None:
                # dense-equivalent capacity plus the trash block; prefix
                # sharing only ever *reduces* usage below this
                pool_blocks = slots * per_slot_blocks + 1
            if pool_blocks < per_slot_blocks + 1:
                raise ValueError(f"pool_blocks {pool_blocks} cannot hold "
                                 "one full-length request (need "
                                 f"{per_slot_blocks + 1})")
        elif opts.kv_dtype != "auto" or opts.paged_kernel:
            raise ValueError("kv_dtype/paged_kernel are paged-pool options; "
                             f"decode_mode={decode_mode!r} keeps its dense "
                             "cache in kv_cache_dtype")
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.opts = opts
        self.decode_mode = decode_mode
        self.block_size = block_size
        self.pool_blocks = pool_blocks
        self.prefix_entries = prefix_entries
        # the freeze/thaw compatibility fingerprint: thawing serialized KV
        # against different weights would silently resume a stale stream,
        # so blobs carry (cfg, opts, params_version) and only thaw when
        # all three match.  Engines sharing a params pytree share its id;
        # callers juggling transient params should pass one explicitly.
        self.params_version = (params_version if params_version is not None
                               else id(params))
        # the per-slot reference loop has no stacked cache to scatter a
        # burst into — it always admits per request; the paged path only
        # has burst admission (its per-request path is the k=1 burst)
        if decode_mode == "per_slot":
            self.prefill_mode = "per_request"
        elif decode_mode == "paged":
            self.prefill_mode = "batched"
        else:
            self.prefill_mode = prefill_mode
        self.sampling = sampling
        self.compile_cache = (compile_cache if compile_cache is not None
                              else GLOBAL_COMPILE_CACHE)
        self.compile_domain = compile_domain
        # observability: recorder defaults to the no-op singleton (hot
        # paths guard on ``recorder.enabled``); the pid names this
        # engine's track in exported traces (the fleet controller passes
        # the device id).  The metrics registry backs ``stats`` and the
        # step-time EWMA — a shared registry makes a fleet's engines
        # aggregate into one namespace.
        self.recorder = recorder
        self.pid = pid if pid is not None else f"engine{next(_ENGINE_SEQ)}"
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.stats = ServeStats(self.metrics)
        self._ewma = self.metrics.ewma("engine.step_time_s", alpha=0.2)
        self._backend_compiles = self.metrics.counter(
            "engine.backend_compiles")
        # paged recurrent stacks: prefix hits refused (the blocks cannot
        # rebuild the state), and the state bytes the live slots hold
        self._prefix_refused = self.metrics.counter(
            "engine.prefix_refused_recurrent")
        self._state_gauge = self.metrics.gauge("engine.state_bytes")
        # decode ticks and prefill calls by the sampler branch their
        # batch takes (``sampler_branch``: the host knows every row's
        # policy, so counting reads nothing off the device)
        self._sampler_counts = {
            b: self.metrics.counter(f"engine.sampler.{b}")
            for b in SAMPLER_BRANCHES}
        self._queue: Deque[Request] = deque()
        self._active: List[Optional[Request]] = [None] * slots
        self.generation = 0
        self._programs: ServePrograms = self._bind_programs()
        self._reset_caches()
        # telemetry: wall-time of recent steps (bounded — engines are
        # long-lived); optional sink called with (step_seconds,
        # tokens_emitted, generation) — the back-end→front-end feedback
        # channel the fleet's TelemetryStore subscribes to.
        self.step_times: Deque[float] = deque(maxlen=2048)
        self.on_step: Optional[Callable[[float, int, int], None]] = None
        # SLO feed: when a tracker is installed (the fleet controller
        # shares its SLOTracker here), the engine reports TTFT at each
        # request's true first token and per-token decode time per step.
        # None (the default) keeps the hot path at one attribute load.
        self.slo = None
        # fault plane: injected OOM failures pending at admission, and
        # the exponential admission backoff they trigger (in steps).
        # All zeros on a healthy engine — the admission hot path is
        # untouched unless a fault is actually injected.
        self._oom_pending = 0
        self._admit_holdoff = 0
        # staged admission (``_admit``): ids of the requests the last
        # admission passed over, and this tick's cohort
        self._passed: set = set()
        self._staged = self._carried = False
        self._oom_backoff = 0
        self.oom_backoff_cap = 8

    def _span(self, name: str, args=None):
        """A span on this engine's ``engine`` track: a profiler
        annotation always, recorder events where one records (see
        :meth:`repro.obs.TraceRecorder.span`)."""
        return self.recorder.span(name, pid=self.pid, tid="engine",
                                  args=args)

    def _state_span(self):
        """``engine.state`` around host-side handling of a paged stack's
        recurrent state (admission, freeze, thaw); nothing elsewhere."""
        if not self._state_leaves:
            return contextlib.nullcontext()
        return self._span("engine.state")

    # ------------------------------------------------------------ programs --
    def _note_compile(self, what: str, **detail) -> None:
        self.stats.recompiles += 1
        if self.recorder.enabled:
            self.recorder.instant("engine.compile", pid=self.pid,
                                  tid="engine", cat="engine",
                                  args={"what": what, **detail})

    def _bind_programs(self) -> ServePrograms:
        entry, fresh = self.compile_cache.entry_for(
            self.cfg, self.opts, self.slots, self.max_seq,
            self.compile_domain)
        if fresh:
            self._note_compile("programs", generation=self.generation)
        return entry

    def _prefill_fn(self, bucket: int) -> Callable:
        fn, fresh = self._programs.prefill(bucket)
        if fresh:
            self._note_compile("prefill", bucket=bucket)
        return fn

    def _prefill_batch_fn(self, bucket: int, k: int) -> Callable:
        fn, fresh = self._programs.prefill_batch(bucket, k)
        if fresh:
            self._note_compile("prefill_batch", bucket=bucket, k=k)
        return fn

    def _paged_decode_fn(self) -> Callable:
        fn, fresh = self._programs.paged_decode(self.pool_blocks,
                                                self.block_size)
        if fresh:
            self._note_compile("paged_decode", pool_blocks=self.pool_blocks,
                               block_size=self.block_size)
        return fn

    def _paged_prefill_fn(self, bucket: int, k: int) -> Callable:
        fn, fresh = self._programs.paged_prefill_batch(
            bucket, k, self.pool_blocks, self.block_size)
        if fresh:
            self._note_compile("paged_prefill_batch", bucket=bucket, k=k)
        return fn

    def _paged_admit_fn(self) -> Callable:
        fn, fresh = self._programs.paged_admit()
        if fresh:
            self._note_compile("paged_admit")
        return fn

    def _thaw_scatter_fn(self, nblk: int) -> Callable:
        fn, fresh = self._programs.thaw_scatter(nblk, self.pool_blocks,
                                                self.block_size)
        if fresh:
            self._note_compile("thaw_scatter", nblk=nblk)
        return fn

    def _copy_block_fn(self) -> Callable:
        fn, fresh = self._programs.copy_block(self.pool_blocks,
                                              self.block_size)
        if fresh:
            self._note_compile("copy_block")
        return fn

    def _reset_caches(self) -> None:
        self._state_leaves, self._state_slot_bytes = (), 0
        # slots whose device temperature may be > 0: a freed one is
        # cooled (``_cool_free_slots``) before the next batched decode
        self._slot_hot = np.zeros(self.slots, bool)
        if self.decode_mode == "batched":
            self._cache = init_slot_cache(self.cfg, self.slots, self.max_seq,
                                          self.opts)
        elif self.decode_mode == "paged":
            self._cache = init_paged_slot_cache(self.cfg, self.slots,
                                                self.max_seq, self.opts)
            # recurrent state beside the pool (hybrid_moe): per-slot leaves
            self._state_leaves = tuple(n for n in STATE_LEAVES
                                       if n in self._cache)
            self._state_slot_bytes = sum(
                self._cache[n].nbytes // self.slots
                for n in self._state_leaves)
            self._pool = init_paged_pool(self.cfg, self.pool_blocks,
                                         self.block_size, self.opts)
            self._blocks = BlockPool(self.slots, self.pool_blocks,
                                     self.block_size, self.max_seq)
            self._prefix = PrefixCache(self.prefix_entries)
            # host-authoritative next-write position per slot (mirrors the
            # device ``pos`` leaf; drives tail-block growth + freezing)
            self._slot_pos = [0] * self.slots
            # admission sequence per slot: preemption under pool pressure
            # evicts the youngest admission first
            self._slot_seq = [0] * self.slots
            self._admit_seq = itertools.count(1)
        else:
            self._caches = [init_cache(self.cfg, 1, self.max_seq, self.opts)
                            for _ in range(self.slots)]

    @property
    def block_pool(self) -> Optional[BlockPool]:
        """The host-side block allocator (``None`` off the paged path) —
        exposed so tests and benches can assert refcounts/sharing."""
        return self._blocks if self.decode_mode == "paged" else None

    # ------------------------------------------------------------- intake --
    def submit(self, req: Request) -> None:
        if not req.arrived_s:
            req.arrived_s = time.perf_counter()
        if self.recorder.enabled:
            # stamped with the exact arrival float, so span-derived TTFT
            # (first_token − queued) equals the legacy subtraction bit
            # for bit
            self.recorder.instant("req.queued", pid=self.pid, tid="queue",
                                  cat="request", wall_s=req.arrived_s,
                                  args={"rid": req.rid,
                                        "prompt_len": len(req.prompt)})
        self._queue.append(req)

    @property
    def has_work(self) -> bool:
        """True while any request is in flight or waiting."""
        return any(r is not None for r in self._active) or bool(self._queue)

    def _bucket(self, n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return min(b, self.max_seq)

    def _k_bucket(self, k: int) -> int:
        """Round a burst size up to its program bucket: powers of two,
        capped at the slot count (mixed burst sizes then share a handful
        of compiled admission programs)."""
        b = 1
        while b < k:
            b *= 2
        return min(b, self.slots)

    def _sampling_of(self, req: Request) -> SamplingOpts:
        return req.sampling if req.sampling is not None else self.sampling

    def _count_sampler(self, policies) -> None:
        """Count one batched sampler call under the branch its rows'
        ``policies`` take on the device."""
        self._sampler_counts[sampler_branch(policies,
                                            self.cfg.vocab_size)].inc()

    def _cool_free_slots(self) -> None:
        """Zero the device temperature of free slots that last held a
        sampling request, so a batch of greedy requests stays on the
        sampler's argmax branch.  A no-op while no slot ever sampled."""
        if not self._slot_hot.any():
            return
        stale = self._slot_hot & np.fromiter(
            (r is None for r in self._active), bool, self.slots)
        if stale.any():
            self._cache = self._programs.cool_slots(self._cache,
                                                    jnp.asarray(stale))
            self._slot_hot &= ~stale

    # ------------------------------------------------------------ stepping --
    def _gather_burst(self, limit: int):
        """Pop the head request plus every same-bucket waiter behind it
        (up to ``limit``) off the queue.  The head anchors the bucket, so
        an earlier waiter from another bucket is always admitted before
        anything behind it — later same-bucket arrivals can share its
        burst's free slots but never displace it.  Budget-spent requests
        encountered on the way complete inline; passed-over requests keep
        their relative order at the queue head.  While admission is
        staged (:meth:`_admit`) a burst takes only waiters of the head's
        cohort.  Returns ``(bucket, requests)``."""
        head = self._queue.popleft()
        cohort = self._in_cohort(head)
        bucket = self._bucket(len(head.prompt))
        batch = [head]
        if limit > 1:
            kept: List[Request] = []
            while self._queue and len(batch) < limit:
                r = self._queue.popleft()
                if len(r.generated) >= r.max_new_tokens:
                    r.done = True
                    continue
                if r.frozen is not None:
                    # frozen state thaws (or falls back) only at the queue
                    # head — bursting it through prefill here would drop
                    # its generated suffix from the bucket computation
                    kept.append(r)
                    continue
                if self._bucket(len(r.prompt)) == bucket \
                        and self._in_cohort(r) == cohort:
                    batch.append(r)
                else:
                    kept.append(r)
            for r in reversed(kept):
                self._queue.appendleft(r)
        return bucket, batch

    def _emit_first(self, req: Request, token: int, stamp: float,
                    free: List[int], slot: int) -> bool:
        """Book-keep a request's prefill token; returns True when the
        request stays active in ``slot`` (False = budget completed at
        prefill, slot returned to the free pool)."""
        req.generated.append(token)
        if req.first_token_s is None:
            # keep the original stamp across swap re-admissions: TTFT is
            # submit→first token, not submit→latest re-prefill
            req.first_token_s = stamp
            if self.slo is not None:
                self.slo.observe("ttft", stamp - req.arrived_s)
        self.stats.prefills += 1
        self.stats.tokens_out += 1
        if self._sampling_of(req).temperature > 0:
            self.stats.sampled_tokens += 1
            self._slot_hot[slot] = True  # admission wrote its temperature
        rec = self.recorder
        if rec.enabled:
            # one first_token instant per *admission* (a swap re-admission
            # emits another, with the re-prefill's stamp — first_token_s
            # above keeps the original), one slot-occupancy span begin
            tid = f"slot{slot}"
            rec.instant("req.first_token", pid=self.pid, tid=tid,
                        cat="request", wall_s=stamp,
                        args={"rid": req.rid, "token": token})
            rec.begin("req.slot", pid=self.pid, tid=tid, cat="request",
                      wall_s=stamp, args={"rid": req.rid})
        if len(req.generated) >= req.max_new_tokens:
            req.done = True          # prefill token completed the budget
            if rec.enabled:
                rec.end("req.slot", pid=self.pid, tid=f"slot{slot}",
                        cat="request", wall_s=stamp,
                        args={"rid": req.rid, "reason": "done_at_prefill",
                              "tokens": len(req.generated)})
            free.append(slot)
            return False
        self._active[slot] = req
        return True

    def _truncate(self, req: Request, bucket: int) -> None:
        if len(req.prompt) > bucket:
            # prompt exceeds max_seq (e.g. a swap re-queue whose prompt
            # grew by the generated prefix): keep the newest context
            req.prompt = req.prompt[-bucket:]

    def _admit_burst(self, batch: List[Request], bucket: int,
                     free: List[int]) -> None:
        """ONE jitted call admits the whole burst: stacked ``(k, bucket)``
        prompts are prefilled together and every row's cache + sampling
        state is scattered into its slot on device.  Bursts smaller than
        their k-bucket are padded with leading throwaway rows aimed at the
        first real slot — written first, overwritten by the real row."""
        k = len(batch)
        kb = self._k_bucket(k)
        pad = kb - k
        with self._span("engine.prefill",
                        args=lambda: {"bucket": bucket, "k": k,
                                      "k_bucket": kb,
                                      "rids": [r.rid for r in batch]}):
            slots_for = [free.pop(0) for _ in range(k)]
            toks = np.zeros((kb, bucket), np.int32)
            keys = np.zeros((kb, 2), np.uint32)
            temps = np.zeros((kb,), np.float32)
            top_ks = np.zeros((kb,), np.int32)
            slot_ids = np.full((kb,), slots_for[0], np.int32)
            for i, req in enumerate(batch):
                self._truncate(req, bucket)
                row = pad + i
                toks[row, bucket - len(req.prompt):] = req.prompt  # left-pad
                s = self._sampling_of(req)
                keys[row] = request_key(s.seed, req.rid, len(req.generated))
                temps[row] = s.temperature
                top_ks[row] = s.top_k
                slot_ids[row] = slots_for[i]
            if self.decode_mode == "paged":
                nblk = bucket // self.block_size
                # pad rows scatter into the trash block; real rows into
                # fresh private blocks (the pool cap in _admit_paged_head
                # guarantees the allocation succeeds)
                dest = np.zeros((kb, nblk), np.int32)
                for i, req in enumerate(batch):
                    ids = self._blocks.alloc(nblk)
                    dest[pad + i] = ids
                    for j, b in enumerate(ids):
                        self._blocks.assign(slots_for[i], j, b)
                fn = self._paged_prefill_fn(bucket, kb)
                first, last, self._cache, self._pool = fn(
                    self.params, self._cache, self._pool, jnp.asarray(toks),
                    jnp.asarray(slot_ids), jnp.asarray(keys),
                    jnp.asarray(temps), jnp.asarray(top_ks),
                    jnp.asarray(dest))
            else:
                last = None
                fn = self._prefill_batch_fn(bucket, kb)
                first, self._cache = fn(self.params, self._cache,
                                        jnp.asarray(toks),
                                        jnp.asarray(slot_ids),
                                        jnp.asarray(keys), jnp.asarray(temps),
                                        jnp.asarray(top_ks))
            with self._span("engine.wait"):
                first = jax.device_get(first)
            self.stats.prefill_calls += 1
            self._count_sampler(self._sampling_of(r) for r in batch)
            stamp = time.perf_counter()
        for i, req in enumerate(batch):
            slot = slots_for[i]
            if self.decode_mode == "paged":
                # dedup freshly written prompt blocks against live blocks
                # holding the same padded-prefix chain hash, then cache
                # the whole prefill for prefix-skip re-admission
                padded = toks[pad + i]
                self._blocks.dedup_slot_prefix(
                    slot, block_hash_chain(padded, self.block_size,
                                           salt=self.params_version))
                self._slot_pos[slot] = bucket
                self._slot_seq[slot] = next(self._admit_seq)
                if self.prefix_entries > 0 and self._state_leaves:
                    # the state is not kept with the prefix: the entry
                    # only marks the prompt seen, holding no blocks
                    with self._state_span():
                        self._prefix.insert(
                            self._prefix.key_of(padded, self.params_version),
                            PrefixEntry(block_ids=(), logits_row=None,
                                        leaves={}, pos=bucket),
                            self._blocks)
                elif self.prefix_entries > 0:
                    self._prefix.insert(
                        self._prefix.key_of(padded, self.params_version),
                        PrefixEntry(
                            block_ids=tuple(
                                int(b) for b in
                                self._blocks.tables[slot, :nblk]),
                            logits_row=last[pad + i],
                            leaves=self._snapshot_slot_leaves(slot),
                            pos=bucket),
                        self._blocks)
            alive = self._emit_first(req, int(first[pad + i]), stamp, free,
                                     slot)
            if self.decode_mode == "paged" and not alive:
                # budget completed at prefill: the slot's references go,
                # but a cached prefix entry keeps the blocks live
                self._blocks.release_slot(slot)

    def _snapshot_slot_leaves(self, slot: int) -> dict:
        """Host copies of one slot's non-KV, non-sampling cache leaves
        (batch=1 layout) — the state a prefix-cache re-admission must
        restore alongside the shared blocks."""
        with self._span("engine.wait"):
            return {name: np.asarray(jax.device_get(leaf[slot]))
                    for name, leaf in self._cache.items()
                    if name != "sample"}

    def _admit_from_prefix(self, req: Request, entry: PrefixEntry,
                           free: List[int]) -> None:
        """Admit a request whose padded prompt hit the prefix cache: no
        prefill jit call at all.  Shared blocks are increfed into the
        slot's table, the cached non-KV leaves and the request's own
        sampling state are written to its slot, and the first token is
        sampled from the cached last-position logits row — bit-identical
        to what a real prefill would have produced."""
        slot = free.pop(0)
        for j, bid in enumerate(entry.block_ids):
            self._blocks.incref(bid)
            self._blocks.assign(slot, j, bid)
        s = self._sampling_of(req)
        key = jnp.asarray(request_key(s.seed, req.rid, len(req.generated)))
        temp = jnp.float32(s.temperature)
        top_k = jnp.int32(s.top_k)
        tok, key = self._programs.sample_first(entry.logits_row, key, temp,
                                               top_k)
        row = {name: jnp.asarray(arr) for name, arr in entry.leaves.items()}
        self._cache = self._paged_admit_fn()(self._cache, row,
                                             jnp.int32(slot), key, temp,
                                             top_k)
        self._slot_pos[slot] = entry.pos
        self._slot_seq[slot] = next(self._admit_seq)
        with self._span("engine.wait"):
            tok = int(tok)
        stamp = time.perf_counter()
        if self.recorder.enabled:
            self.recorder.instant("engine.prefix_hit", pid=self.pid,
                                  tid="engine", cat="engine", wall_s=stamp,
                                  args={"rid": req.rid,
                                        "blocks": len(entry.block_ids)})
        if not self._emit_first(req, tok, stamp, free, slot):
            self._blocks.release_slot(slot)

    def _admit_one(self, req: Request, free: List[int]) -> None:
        """Sequential reference admission: one prefill jit call for this
        request, its first token drawn by the same ``sample_logits`` the
        batched paths use."""
        slot = free.pop(0)
        bucket = self._bucket(len(req.prompt))
        self._truncate(req, bucket)
        with self._span("engine.prefill",
                        args=lambda: {"bucket": bucket, "k": 1,
                                      "rids": [req.rid]}):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, bucket - len(req.prompt):] = req.prompt  # left-pad
            cache = init_cache(self.cfg, 1, self.max_seq, self.opts)
            logits, cache = self._prefill_fn(bucket)(
                self.params, cache, jnp.asarray(toks))
            self.stats.prefill_calls += 1
            s = self._sampling_of(req)
            self._count_sampler((s,))
            key = jnp.asarray(request_key(s.seed, req.rid,
                                          len(req.generated)))
            temp = jnp.float32(s.temperature)
            top_k = jnp.int32(s.top_k)
            tok, key = self._programs.sample_first(logits[0, -1], key, temp,
                                                   top_k)
            with self._span("engine.wait"):
                nxt = int(tok)
            stamp = time.perf_counter()
        if not self._emit_first(req, nxt, stamp, free, slot):
            return
        if self.decode_mode == "batched":
            # the stacked side is donated: the slot write is in place
            self._cache = self._programs.admit_slot(
                self._cache, cache, jnp.int32(slot), key, temp, top_k)
        else:
            cache["sample"] = {"key": key, "temp": temp, "top_k": top_k}
            self._caches[slot] = cache

    def inject_oom(self, n: int = 1) -> None:
        """Fault injection: the next ``n`` admission attempts fail as if
        cache allocation OOMed.  The engine responds the way a real
        admission controller would — the request stays queued (zero
        token loss) and admission backs off exponentially (doubling
        hold-off steps, capped at ``oom_backoff_cap``) before retrying,
        so a memory-pressured engine stops hammering the allocator."""
        self._oom_pending += max(int(n), 0)

    def _admit(self) -> None:
        if self._admit_holdoff > 0:
            self._admit_holdoff -= 1
            return
        free = [s for s in range(self.slots) if self._active[s] is None]
        if self._oom_pending > 0 and free and self._queue:
            # injected OOM: this admission attempt fails, the head stays
            # queued untouched, and we back off before trying again
            self._oom_pending -= 1
            self.stats.oom_events += 1
            self._oom_backoff = min(max(2 * self._oom_backoff, 1),
                                    self.oom_backoff_cap)
            self._admit_holdoff = self._oom_backoff
            if self.recorder.enabled:
                self.recorder.instant(
                    "engine.oom", pid=self.pid, tid="engine",
                    cat="engine",
                    args={"backoff_steps": self._admit_holdoff,
                          "queued": len(self._queue)})
            return
        # Staged admission.  While slots decode, every prefill call stalls
        # each of their next tokens, so a tick prefills one cohort: the
        # requests the last admission passed over (all of them, so none
        # waits twice), or else the head's bucket among the rest, whose
        # other buckets wait a tick.  A burst never mixes the cohorts, so
        # none is larger than what arrived between two admissions.
        self._staged = self.prefill_mode == "batched" and \
            len(free) < self.slots
        self._carried = self._staged and any(
            id(r) in self._passed for r in self._queue)
        calls = self.stats.prefill_calls
        admitted = False
        while free and self._queue:
            head = self._queue[0]
            if self._staged and (
                    not self._in_cohort(head)
                    or (not self._carried
                        and self.stats.prefill_calls > calls)):
                break               # the rest wait for the next tick
            if len(head.generated) >= head.max_new_tokens:
                # re-queued after a swap with its budget already spent (or
                # submitted with max_new_tokens=0): emitting another prefill
                # token would overshoot the budget and double-count it.
                self._queue.popleft()
                head.done = True
                continue
            if head.frozen is not None:
                if self.can_thaw(head.frozen):
                    if not self._thaw_capacity_ok(head.frozen):
                        # pool backpressure: decode frees blocks.  A thaw
                        # must never *preempt* to fit — a preempted
                        # victim at the head would thaw by preempting
                        # right back, an admission livelock
                        break
                    self._queue.popleft()
                    self._thaw_into_slot(head, free.pop(0))
                    admitted = True
                    continue
                # fingerprint mismatch: drop the blob and re-prefill
                # prompt+generated (the legacy zero-token-loss requeue)
                self._discard_frozen(head)
            if self.decode_mode == "paged":
                if self._admit_paged_head(head, free):
                    admitted = True
                    continue
                break               # pool exhausted: wait for decode frees
            if self.prefill_mode == "batched":
                bucket, batch = self._gather_burst(len(free))
                self._admit_burst(batch, bucket, free)
            else:
                self._queue.popleft()
                self._admit_one(head, free)
            admitted = True
        self._passed = {id(r) for r in self._queue}
        self._staged = False
        if admitted:
            self._oom_backoff = 0     # a successful admission heals

    def _in_cohort(self, req: Request) -> bool:
        """Whether staged admission may prefill ``req`` this tick (every
        request, where admission is not staged)."""
        return not self._staged or (id(req) in self._passed) == self._carried

    def _admit_paged_head(self, head: Request, free: List[int]) -> bool:
        """Admit the head request (plus any same-bucket burst) into the
        paged cache.  Returns False when the pool cannot cover the head's
        prompt blocks even after evicting cached prefixes — admission
        then waits for decode to free blocks (backpressure, not loss)."""
        bucket = self._bucket(len(head.prompt))
        nblk = bucket // self.block_size
        entry = self._prefix.lookup(
            self._prefix.key_of(self._padded_prompt(head, bucket),
                                self.params_version))
        if entry is not None and self._state_leaves:
            # the blocks hold the attention layers' KV, not the recurrent
            # state the prompt left: refuse the reuse, prefill instead
            self._prefix_refused.inc()
        elif entry is not None:
            self._queue.popleft()
            self._admit_from_prefix(head, entry, free)
            return True
        if self._blocks.free_blocks < nblk:
            self._prefix.evict_for_blocks(nblk, self._blocks)
        max_k = self._blocks.free_blocks // nblk
        if max_k == 0:
            return False
        bucket, batch = self._gather_burst(min(len(free), max_k))
        self._admit_burst(batch, bucket, free)
        return True

    def _padded_prompt(self, req: Request, bucket: int) -> np.ndarray:
        """The left-padded prompt row exactly as prefill sees it — the
        prefix-sharing unit (KV content is a pure function of it)."""
        row = np.zeros(bucket, np.int32)
        prompt = req.prompt[-bucket:] if len(req.prompt) > bucket \
            else req.prompt
        row[bucket - len(prompt):] = prompt
        return row

    def _decode_batched(self) -> int:
        if not any(r is not None for r in self._active):
            return 0
        with self._span("engine.dispatch"):
            tokens = np.zeros(self.slots, np.int32)
            policies = {}
            for slot, req in enumerate(self._active):
                if req is not None:
                    tokens[slot] = req.generated[-1]
                    s = self._sampling_of(req)
                    policies[id(s)] = s
            self._cool_free_slots()
            # an all-greedy batch takes the sampler's argmax branch on
            # the device
            self._count_sampler(policies.values())
            nxt, pos, self._cache = self._programs.decode(
                self.params, self._cache, jnp.asarray(tokens))
        return self._bookkeep_decode(nxt, pos)

    def _bookkeep_decode(self, nxt, pos) -> int:
        """Shared post-step bookkeeping for the batched and paged decode
        paths: one bulk device→host transfer, per-slot token append,
        finish detection and trace emission."""
        with self._span("engine.wait"):
            nxt, pos = jax.device_get((nxt, pos))   # one bulk transfer
        paged = self.decode_mode == "paged"
        emitted = 0
        rec = self.recorder
        with self._span("engine.bookkeep"):
            stamp = time.perf_counter() if rec.enabled else 0.0
            for slot, req in enumerate(self._active):
                if req is None:      # masked slot: decoded, output ignored
                    continue
                req.generated.append(int(nxt[slot]))
                emitted += 1
                if paged:
                    self._slot_pos[slot] = int(pos[slot])
                if self._sampling_of(req).temperature > 0:
                    self.stats.sampled_tokens += 1
                if rec.enabled:
                    rec.instant("req.decode", pid=self.pid,
                                tid=f"slot{slot}", cat="request",
                                wall_s=stamp,
                                args={"rid": req.rid,
                                      "token": int(nxt[slot])})
                if len(req.generated) >= req.max_new_tokens \
                        or int(pos[slot]) >= self.max_seq - 1:
                    req.done = True
                    self._active[slot] = None
                    if paged:
                        self._blocks.release_slot(slot)
                    if rec.enabled:
                        rec.end("req.slot", pid=self.pid, tid=f"slot{slot}",
                                cat="request", wall_s=stamp,
                                args={"rid": req.rid, "reason": "finished",
                                      "tokens": len(req.generated)})
        return emitted

    # ------------------------------------------------------ paged decode --
    def _alloc_blocks_reclaiming(self, n: int,
                                 keep_slot: Optional[int] = None
                                 ) -> Optional[List[int]]:
        """Allocate ``n`` blocks, reclaiming under pressure: first evict
        cached prefix entries (LRU), then preempt the youngest-admitted
        active slot (freeze → requeue head, zero token loss) — never
        ``keep_slot``, the slot the allocation is for."""
        ids = self._blocks.alloc(n)
        while ids is None:
            if self._prefix.evict_for_blocks(n, self._blocks) == 0:
                victims = [s for s, r in enumerate(self._active)
                           if r is not None and s != keep_slot]
                if not victims:
                    return None
                victim = max(victims, key=lambda s: self._slot_seq[s])
                req = self._active[victim]
                req.frozen = self._freeze_slot(victim, reason="preempt")
                self._queue.appendleft(req)
                self.stats.requeues += 1
            ids = self._blocks.alloc(n)
        return ids

    def _ensure_tail_blocks(self) -> None:
        """Pre-decode growth pass: every active slot must own a private
        block for the row this step writes.  Buckets are block-aligned,
        so growth happens exactly at block boundaries; the copy-on-write
        branch guards the shared-block invariant (a shared block is
        never written in place)."""
        bs = self.block_size
        for slot, req in enumerate(self._active):
            if req is None:
                continue
            idx = self._slot_pos[slot] // bs
            if idx >= self._blocks.blocks_per_slot:
                continue             # finishes at the max_seq bound
            bid = int(self._blocks.tables[slot, idx])
            if bid != TRASH_BLOCK and self._blocks.refs[bid] <= 1:
                continue             # private tail already in place
            ids = self._alloc_blocks_reclaiming(1, keep_slot=slot)
            if ids is None:          # only this slot is active and the
                continue             # pool is drained; write lands in
                                     # trash and the request requeues
            if bid != TRASH_BLOCK:   # copy-on-write off a shared block
                self._pool = self._copy_block_fn()(
                    self._pool, jnp.int32(bid), jnp.int32(ids[0]))
                self._blocks.decref(bid)
            self._blocks.assign(slot, idx, ids[0])

    def _decode_paged(self) -> int:
        if not any(r is not None for r in self._active):
            return 0
        with self._span("engine.blocks"):
            self._ensure_tail_blocks()
        with self._span("engine.dispatch"):
            tokens = np.zeros(self.slots, np.int32)
            policies = {}
            for slot, req in enumerate(self._active):
                if req is not None:
                    tokens[slot] = req.generated[-1]
                    s = self._sampling_of(req)
                    policies[id(s)] = s
            self._cool_free_slots()
            self._count_sampler(policies.values())
            # block tables are runtime data: constant (slots, max_seq/bs)
            # shape, so occupancy/sharing churn reuses one compiled program
            nxt, pos, self._cache, self._pool = self._paged_decode_fn()(
                self.params, self._cache, self._pool, jnp.asarray(tokens),
                jnp.asarray(self._blocks.tables))
        return self._bookkeep_decode(nxt, pos)

    def lower_decode(self) -> "jax.stages.Lowered":
        """The paged decode program of the current binding, lowered for
        this engine's state shapes; ``.compile().as_text()`` shows what
        the device runs (the Pallas kernel appears as a custom call)."""
        if self.decode_mode != "paged":
            raise ValueError("lower_decode covers decode_mode='paged'")
        return self._paged_decode_fn().lower(
            self.params, self._cache, self._pool,
            jnp.zeros(self.slots, jnp.int32),
            jnp.asarray(self._blocks.tables))

    def _decode_per_slot(self) -> int:
        emitted = 0
        rec = self.recorder
        for slot, req in enumerate(self._active):
            if req is None:
                continue
            tok = jnp.asarray(req.generated[-1], jnp.int32)
            nxt, cache = self._programs.sample_ref(
                self.params, self._caches[slot], tok)
            self._caches[slot] = cache
            req.generated.append(int(nxt))
            emitted += 1
            if self._sampling_of(req).temperature > 0:
                self.stats.sampled_tokens += 1
            if rec.enabled:
                rec.instant("req.decode", pid=self.pid, tid=f"slot{slot}",
                            cat="request",
                            args={"rid": req.rid, "token": int(nxt)})
            if len(req.generated) >= req.max_new_tokens \
                    or int(cache["pos"]) >= self.max_seq - 1:
                req.done = True
                self._active[slot] = None
                if rec.enabled:
                    rec.end("req.slot", pid=self.pid, tid=f"slot{slot}",
                            cat="request",
                            args={"rid": req.rid, "reason": "finished",
                                  "tokens": len(req.generated)})
        return emitted

    def step(self) -> int:
        """One engine tick: admit waiting requests, decode one token for
        every active slot.  Returns number of tokens emitted."""
        with counting(self._backend_compiles), self._span("engine.tick"):
            with self._span("engine.admit"):
                self._admit()
            # time only the decode sweep: prefill/compile costs would
            # otherwise masquerade as decode-step latency in the telemetry
            # channel
            with self._span("engine.step",
                            args=lambda: {"generation": self.generation}):
                t0 = time.perf_counter()
                if self.decode_mode == "batched":
                    emitted = self._decode_batched()
                elif self.decode_mode == "paged":
                    emitted = self._decode_paged()
                else:
                    emitted = self._decode_per_slot()
                self.stats.steps += 1
                self.stats.tokens_out += emitted
                dt = time.perf_counter() - t0
            if self._state_slot_bytes:
                self._state_gauge.set(self._state_slot_bytes * sum(
                    r is not None for r in self._active))
            self.step_times.append(dt)
            self._ewma.update(dt)
            if self.slo is not None and emitted:
                # every active slot advanced one token this step, so the
                # step wall time is each of those tokens' inter-token time
                self.slo.observe("tpot", dt, n=emitted)
            if self.on_step is not None:
                self.on_step(dt, emitted, self.generation)
        return emitted

    @property
    def step_time_ewma_s(self) -> Optional[float]:
        """Smoothed recent decode-step wall time (seconds), or ``None``
        before the first step.  This is the step-timing hook the fleet's
        event scheduler consults: an engine-backed device's next wake is
        its envelope period *plus* ``steps_per_tick × step_time_ewma_s``,
        so devices whose engines slow down under load automatically tick
        less often.  A view over the registry's ``engine.step_time_s``
        EWMA gauge (``alpha=0.2`` reproduces the historical
        ``0.8·prev + 0.2·dt`` update bit for bit)."""
        return self._ewma.value

    def drain(self, max_steps: int = 10_000) -> None:
        while self.has_work and max_steps:
            self.step()
            max_steps -= 1

    # ---------------------------------------------------------- freeze/thaw --
    @property
    def fingerprint(self) -> tuple:
        """The freeze/thaw compatibility fingerprint: a
        :class:`FrozenRequest` thaws here iff its fingerprint equals
        this (same config, same runtime options, same weights).

        Pool-*storage* options are normalized out: blobs are densified
        in ``kv_cache_dtype`` regardless of how the pool stores them, so
        an ``kv_dtype="int8"`` engine's blob thaws on a bf16-pool peer
        (and vice versa — thaw re-quantizes), and ``paged_kernel`` never
        touches blob layout at all.  Cross-``kv_dtype`` continuations
        are token-loss-free and re-prefill-free but decode with the
        destination's numerics, so they are not bit-identical to an
        uninterrupted source run."""
        opts = replace(self.opts, kv_dtype="auto", paged_kernel=False)
        return (self.cfg, opts, self.params_version)

    def can_thaw(self, frozen: Optional[FrozenRequest]) -> bool:
        """Whether a frozen blob can resume on this engine without
        re-prefill.  A blob frozen at the sequence bound has nowhere
        left to write, so it falls back to the requeue path (which
        truncates to the newest context)."""
        return (frozen is not None
                and frozen.fingerprint == self.fingerprint
                and frozen.pos < self.max_seq - 1)

    def _freeze_slot(self, slot: int, reason: str = "freeze"
                     ) -> FrozenRequest:
        """Serialize ``slot``'s in-flight state into a host-side
        :class:`FrozenRequest` and vacate the slot.  KV is *densified*
        (paged blocks gathered, rows trimmed to ``pos``) so the blob is
        portable across block sizes and into dense or per-slot engines.
        The sampling subtree carries the slot's **advanced** PRNG key, so
        the thawed stream continues bit for bit."""
        req = self._active[slot]
        with self._span("engine.wait"):
            if self.decode_mode == "per_slot":
                cache = self._caches[slot]
                pos = int(jax.device_get(cache["pos"]))
                leaves = {name: np.asarray(jax.device_get(leaf))
                          for name, leaf in cache.items()
                          if name != "sample"}
                sample = {name: np.asarray(jax.device_get(v))
                          for name, v in cache["sample"].items()}
            else:
                pos = (self._slot_pos[slot] if self.decode_mode == "paged"
                       else int(jax.device_get(self._cache["pos"][slot])))
                with self._state_span():
                    leaves = {name: np.asarray(jax.device_get(leaf[slot]))
                              for name, leaf in self._cache.items()
                              if name != "sample"}
                sample = {name: np.asarray(jax.device_get(arr[slot]))
                          for name, arr in self._cache["sample"].items()}
            for name in _SEQ_TRIM_LEAVES:
                if name in leaves:
                    leaves[name] = leaves[name][:, :, :pos]
            if self.decode_mode == "paged":
                # gather this slot's blocks into dense (n_attn, 1, pos, ...)
                # KV; int8 pools dequantize first so the blob stays portable
                # in kv_cache_dtype (any engine can thaw it, re-quantizing
                # or not)
                bs = self.block_size
                nblk = blocks_needed(pos, bs)
                ids = self._blocks.tables[slot, :nblk]
                for name in ("k", "v"):
                    at = jnp.asarray(ids)
                    blocks = self._pool[name][at]
                    if name + "_scale" in self._pool:
                        blocks = kv_dequant_rows(
                            blocks, self._pool[name + "_scale"][at],
                            dtype_of(self.opts.kv_cache_dtype))
                    g = np.asarray(jax.device_get(blocks))
                    n_attn, kvh, hd = g.shape[1], g.shape[3], g.shape[4]
                    dense = g.transpose(1, 0, 2, 3, 4).reshape(
                        n_attn, nblk * bs, kvh, hd)[:, :pos]
                    leaves[name] = dense[:, None]
        frozen = FrozenRequest(rid=req.rid, pos=pos,
                               consumed=len(req.generated), leaves=leaves,
                               sample=sample, fingerprint=self.fingerprint,
                               reason=reason)
        self.stats.freezes += 1
        rec = self.recorder
        if rec.enabled:
            stamp = time.perf_counter()
            rec.instant("req.freeze", pid=self.pid, tid=f"slot{slot}",
                        cat="request", wall_s=stamp,
                        args={"rid": req.rid, "reason": reason, "pos": pos})
            rec.end("req.slot", pid=self.pid, tid=f"slot{slot}",
                    cat="request", wall_s=stamp,
                    args={"rid": req.rid, "reason": reason,
                          "tokens": len(req.generated)})
        self._active[slot] = None
        if self.decode_mode == "paged":
            self._blocks.release_slot(slot)
        return frozen

    def freeze(self, rid: int) -> Optional[Request]:
        """Freeze the active request with id ``rid`` and hand it back
        (blob attached as ``req.frozen``); the caller owns it — submit
        it to a compatible engine via :meth:`thaw`.  Returns ``None``
        when ``rid`` is not currently decoding here."""
        for slot, r in enumerate(self._active):
            if r is not None and r.rid == rid:
                r.frozen = self._freeze_slot(slot, reason="freeze")
                return r
        return None

    def freeze_all(self, reason: str = "freeze") -> List[Request]:
        """Freeze every in-flight request (slot order) and hand the
        detached requests back — the fleet's migration primitive."""
        out: List[Request] = []
        for slot, r in enumerate(self._active):
            if r is not None:
                r.frozen = self._freeze_slot(slot, reason=reason)
                out.append(r)
        return out

    def thaw(self, req: Request) -> bool:
        """Accept a frozen request: queued at the *head*, it resumes with
        zero re-prefill on the next admission sweep if its blob matches
        this engine's fingerprint.  Returns False when the blob is
        incompatible — it is dropped and the request re-admits through
        the legacy prompt+generated re-prefill path (still zero token
        loss, but a prefill call)."""
        ok = self.can_thaw(req.frozen)
        if not ok and req.frozen is not None:
            self._discard_frozen(req)
        self._queue.appendleft(req)
        return ok

    def _discard_frozen(self, req: Request) -> None:
        """Fingerprint-mismatch fallback: fold the generated suffix into
        the prompt (the legacy zero-token-loss requeue contract) and drop
        the blob — the request re-admits via ordinary prefill, its PRNG
        key folded with its consumed count so the stream advances
        deterministically instead of replaying."""
        req.prompt = np.concatenate([np.asarray(req.prompt, np.int32),
                                     np.asarray(req.generated, np.int32)])
        req.frozen = None

    def _padded_to(self, src: np.ndarray, shape, dtype) -> jnp.ndarray:
        """Zero-pad a trimmed blob leaf back to a full cache leaf."""
        if tuple(src.shape) == tuple(shape):
            return jnp.asarray(src, dtype)
        buf = np.zeros(shape, dtype)
        buf[tuple(slice(0, d) for d in src.shape)] = src
        return jnp.asarray(buf)

    def _thaw_capacity_ok(self, frozen: FrozenRequest) -> bool:
        """Paged-mode admission guard: can the pool cover this blob's
        blocks right now (after evicting cached prefixes if needed)?
        Off the paged path there is nothing to allocate."""
        if self.decode_mode != "paged":
            return True
        need = blocks_needed(frozen.pos, self.block_size)
        if self._blocks.free_blocks < need:
            self._prefix.evict_for_blocks(need, self._blocks)
        return self._blocks.free_blocks >= need

    def _thaw_into_slot(self, req: Request, slot: int) -> None:
        """Re-materialize a frozen request in ``slot`` with **zero
        re-prefill**: blob leaves are zero-padded back to full cache
        shape (padding beyond ``pos`` is never read unmasked) and the
        slot resumes decoding from the blob's advanced sampling key."""
        fz = req.frozen
        ids = None
        if self.decode_mode == "paged":
            nblk = blocks_needed(fz.pos, self.block_size)
            ids = self._alloc_blocks_reclaiming(nblk, keep_slot=slot)
            if ids is None:
                raise RuntimeError("paged pool cannot hold one thawed "
                                   "request — pool_blocks misconfigured")
            for j, b in enumerate(ids):
                self._blocks.assign(slot, j, b)
            self._slot_pos[slot] = fz.pos
            self._slot_seq[slot] = next(self._admit_seq)
        # the blob's uploads and the programs that write them into place
        with self._span("engine.wait"), self._state_span():
            self._upload_frozen(fz, slot, ids)
        req.frozen = None
        self._active[slot] = req
        if fz.sample["temp"] > 0:
            self._slot_hot[slot] = True
        self.stats.thaws += 1
        if self.recorder.enabled:
            stamp = time.perf_counter()
            self.recorder.instant("req.thaw", pid=self.pid,
                                  tid=f"slot{slot}", cat="request",
                                  wall_s=stamp,
                                  args={"rid": req.rid, "pos": fz.pos,
                                        "consumed": fz.consumed})
            self.recorder.begin("req.slot", pid=self.pid, tid=f"slot{slot}",
                                cat="request", wall_s=stamp,
                                args={"rid": req.rid})

    def _upload_frozen(self, fz: FrozenRequest, slot: int,
                       ids: Optional[List[int]]) -> None:
        """Write a blob's state into ``slot`` (and, paged, its KV into
        the blocks ``ids``)."""
        key = jnp.asarray(fz.sample["key"])
        temp = jnp.asarray(fz.sample["temp"], jnp.float32)
        top_k = jnp.asarray(fz.sample["top_k"], jnp.int32)
        if self.decode_mode == "per_slot":
            cache = init_cache(self.cfg, 1, self.max_seq, self.opts)
            cache = {name: self._padded_to(fz.leaves[name], leaf.shape,
                                           leaf.dtype)
                     for name, leaf in cache.items()}
            cache["sample"] = {"key": key, "temp": temp, "top_k": top_k}
            self._caches[slot] = cache
            return
        row = {name: self._padded_to(fz.leaves[name], leaf.shape[1:],
                                     leaf.dtype)
               for name, leaf in self._cache.items() if name != "sample"}
        if self.decode_mode == "batched":
            self._cache = self._programs.admit_slot(
                self._cache, row, jnp.int32(slot), key, temp, top_k)
            return
        bs = self.block_size
        # program count stays bounded: the scatter is keyed on the
        # *bucketed* block count, trailing ids aimed at trash
        nblk_prog = self._bucket(fz.pos) // bs
        rows = {}
        for name in ("k", "v"):
            src = fz.leaves[name][:, 0]          # (n_attn, pos, kvh, hd)
            n_attn, _, kvh, hd = src.shape
            buf = np.zeros((n_attn, nblk_prog * bs, kvh, hd), src.dtype)
            buf[:, :fz.pos] = src
            rows[name] = jnp.asarray(
                buf.reshape(n_attn, nblk_prog, bs, kvh, hd)
                .transpose(1, 0, 2, 3, 4))
        ids_arr = np.full(nblk_prog, TRASH_BLOCK, np.int32)
        ids_arr[:len(ids)] = ids
        self._pool = self._thaw_scatter_fn(nblk_prog)(
            self._pool, rows["k"], rows["v"], jnp.asarray(ids_arr))
        self._cache = self._paged_admit_fn()(self._cache, row,
                                             jnp.int32(slot), key, temp,
                                             top_k)

    def drain_waiting(self) -> List[Request]:
        """Detach every *waiting* (queued, not yet admitted) request in
        FIFO order — the migration caller re-submits them on the
        destination engine alongside the frozen in-flight ones."""
        out = list(self._queue)
        self._queue.clear()
        return out

    # ----------------------------------------------------------- adaptation --
    def requeue_active(self, reason: str = "requeue") -> int:
        """Re-queue every in-flight request at the head of the queue
        with **zero token loss** — and, since the paging PR, zero
        re-prefill: each request is frozen (KV + sampling state
        serialized host-side) and thaws straight back into a slot when
        its blob matches the engine's fingerprint.  Incompatible blobs
        (e.g. after a variant swap) fall back to the legacy
        prompt+generated re-prefill, whose PRNG key folds the consumed
        count so the stream advances deterministically instead of
        replaying.  Returns the number of requests re-queued."""
        pending: List[Request] = []
        for slot, r in enumerate(self._active):
            if r is not None:
                r.frozen = self._freeze_slot(slot, reason=reason)
                pending.append(r)
        for r in reversed(pending):
            self._queue.appendleft(r)
        self.stats.requeues += len(pending)
        return len(pending)

    def swap_model(self, cfg: ModelConfig, params: Params,
                   opts: RuntimeOptions,
                   params_version: Optional[int] = None) -> None:
        """Middleware hook: switch the serving variant.  Active requests
        are frozen and re-queued; after the caches rebuild they thaw
        with **zero re-prefill** when the new binding matches their blob
        (same cfg/opts/weights — e.g. a placement-driven engine restart),
        and fall back to re-prefilling their generated prefix when the
        variant really changed (retraining-free variant switching).
        Programs come from the compile cache, so swapping back to an
        already-served variant costs zero compiles."""
        with self._span("engine.swap", args=lambda: {
                "generation": self.generation + 1,
                "requeued": sum(r is not None for r in self._active)}):
            self.requeue_active(reason="swap_requeue")
            self.cfg, self.params, self.opts = cfg, params, opts
            self.params_version = (params_version
                                   if params_version is not None
                                   else id(params))
            self.generation += 1
            self._programs = self._bind_programs()
            self._reset_caches()
            # blobs that can't thaw against the new binding re-admit via
            # the legacy path; dropping them up front lets the whole
            # requeue merge into one admission burst instead of k
            # head-of-line fragments (pinned by the swap prefill_calls
            # tests)
            for r in self._queue:
                if r.frozen is not None and not self.can_thaw(r.frozen):
                    self._discard_frozen(r)
