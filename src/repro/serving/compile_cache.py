"""Process-wide cache of jitted serving programs.

A 15-device fleet used to mean ~15 identical decode programs: every
:class:`~repro.serving.engine.ServingEngine` built its own ``jax.jit``
wrappers, and jax's compilation cache keys on function identity, so
nothing was shared.  ``CompileCache`` keys program sets on the things
that actually determine the compiled artifact — ``(cfg, opts, slots,
max_seq, domain)`` — and hands the *same* jitted callables to every
engine that asks, so same-platform fleet members compile once.

Sampling is deliberately **absent** from the key: per-slot temperature,
top-k and PRNG keys are runtime arrays inside the slot-stacked cache
(see :mod:`repro.serving.sampling`), so engines with heterogeneous
sampling policies still share every program.

``domain`` namespaces otherwise-identical keys by compile target
(platform/ISA): in a real deployment a pixel_6 cannot reuse a jetson's
binaries even for the same model, so the fleet controller passes each
device's :attr:`DeviceSpec.compile_domain` here.

Program set per key:

* ``decode``       — one batched sampling step over the slot-stacked
                     cache (``sample_batched_step``: ``decode_step``
                     under ``vmap``, then ``sample_rows`` over the
                     slots), with the cache **donated** so KV/SSM
                     buffers are updated in place instead of copied
                     every token; slots whose temperature is 0 argmax
                     exactly as before, and a batch with no sampling
                     slot argmaxes and nothing else
* ``decode_ref``   — the batch=1 reference decode returning raw logits
                     (kept for equivalence tests and benchmarks)
* ``sample_ref``   — the batch=1 sampling decode (the per-slot loop
                     path); ``decode`` is its batched twin, token for
                     token
* ``sample_first`` — draws a prefill's first token from its last-position
                     logits row (per-request admission path)
* ``admit_slot``   — writes a fresh prefill + its sampling state into one
                     slot of the stacked cache (stacked side donated;
                     slot index traced, so one program covers every slot)
* ``cool_slots``   — zeroes the temperature of the slots a ``(slots,)``
                     mask names (cache donated): a freed slot that
                     sampled must not hold the batch on a costlier
                     sampler branch
* ``prefill(bucket)`` — per-prompt-bucket batch=1 prefill jits, lazy
* ``prefill_batch(bucket, k)`` — ONE-call burst admission: prefill a
                     ``(k, bucket)`` stack of same-bucket prompts and
                     scatter every row into its slot; keyed on the
                     k-bucket so mixed burst sizes reuse a handful of
                     programs instead of recompiling per shape

Paged-mode programs (``decode_mode="paged"``) are lazy dicts keyed on
the pool geometry ``(num_blocks, block_size)`` — block *tables* are
runtime int32 arrays of constant shape, so occupancy, sharing and
admission churn never recompile and the outer cache key stays
``(cfg, opts, slots, max_seq, domain)``:

* ``paged_decode(nb, bs)`` — one batched sampling step (slot cache +
                     pool donated); bit-identical to ``decode``.  With
                     ``opts.paged_kernel`` the program is the
                     kernel step (attention reads blocks through the
                     table — no gather-to-dense detour); ``opts`` is in
                     the cache key, so the selection never aliases
* ``paged_prefill_batch(bucket, k, nb, bs)`` — burst admission that
                     scatters prefilled KV into destination blocks
* ``paged_admit``   — writes non-KV leaves + sampling state into one
                     slot of the paged slot cache (thaw / prefix reuse)
* ``thaw_scatter(nblk, nb, bs)`` — writes a thawed request's densified
                     KV back into freshly allocated blocks
* ``copy_block(nb, bs)`` — copy-on-write block duplication
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax

from repro.models.configs import ModelConfig
from repro.models.model import (admit_slot, batched_prefill_admit,
                                cool_slots, decode_step,
                                paged_copy_block,
                                paged_kernel_sample_batched_step,
                                paged_prefill_admit,
                                paged_sample_batched_step, paged_thaw_write,
                                prefill, sample_batched_step, sample_logits,
                                sample_step)
from repro.models.runtime import RuntimeOptions

Key = Tuple[ModelConfig, RuntimeOptions, int, int, str]


def _jit(name: str, fn: Callable, **jit_kw) -> Callable:
    """``jax.jit`` of ``fn`` under a stable name: the XLA module is
    ``jit_<name>`` in compiled text and profiler traces, not
    ``jit__lambda``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kw)


class ServePrograms:
    """The jitted callables for one (cfg, opts, slots, max_seq, domain)."""

    def __init__(self, cfg: ModelConfig, opts: RuntimeOptions,
                 max_seq: int = 512):
        self._cfg, self._opts, self._max_seq = cfg, opts, max_seq
        # donate the stacked cache: its buffers are rewritten every token,
        # so aliasing input→output storage avoids a full cache copy per step
        self.decode: Callable = _jit(
            "decode", lambda p, c, t: sample_batched_step(p, cfg, c, t, opts),
            donate_argnums=(1,))
        self.decode_ref: Callable = _jit(
            "decode_ref", lambda p, c, t: decode_step(p, cfg, c, t, opts))
        self.sample_ref: Callable = _jit(
            "sample_ref", lambda p, c, t: sample_step(p, cfg, c, t, opts))
        self.sample_first: Callable = _jit(
            "sample_first",
            lambda lg, k, t, tk: sample_logits(lg, k, t, tk, cfg.vocab_size))
        self.admit_slot: Callable = _jit(
            "admit_slot",
            lambda stacked, c, i, k, t, tk: admit_slot(stacked, c, i, k, t,
                                                       tk),
            donate_argnums=(0,))
        self.cool_slots: Callable = _jit(
            "cool_slots", cool_slots, donate_argnums=(0,))
        self._prefills: Dict[int, Callable] = {}
        self._prefill_batches: Dict[Tuple[int, int], Callable] = {}
        self._paged_decodes: Dict[Tuple[int, int], Callable] = {}
        self._paged_prefill_batches: Dict[Tuple[int, int, int, int],
                                          Callable] = {}
        self._paged_admit: Dict[str, Callable] = {}
        self._thaw_scatters: Dict[Tuple[int, int, int], Callable] = {}
        self._copy_blocks: Dict[Tuple[int, int], Callable] = {}

    def prefill(self, bucket: int) -> Tuple[Callable, bool]:
        """The batch=1 prefill jit for one prompt bucket, plus whether this
        call created it (a compile the caller should account for)."""
        fresh = bucket not in self._prefills
        if fresh:
            cfg, opts = self._cfg, self._opts
            self._prefills[bucket] = _jit(
                "prefill", lambda p, c, t: prefill(p, cfg, t, c, opts))
        return self._prefills[bucket], fresh

    def prefill_batch(self, bucket: int, k: int) -> Tuple[Callable, bool]:
        """The one-call burst-admission program for ``(prompt bucket,
        k-bucket)``: prefill ``(k, bucket)`` stacked prompts and scatter
        each row's cache + sampling state into its slot of the (donated)
        slot-stacked cache.  Callers bucket ``k`` (powers of two capped at
        the slot count) so mixed burst sizes share a handful of programs."""
        fresh = (bucket, k) not in self._prefill_batches
        if fresh:
            cfg, opts, max_seq = self._cfg, self._opts, self._max_seq
            self._prefill_batches[(bucket, k)] = _jit(
                "prefill_batch",
                lambda p, st, t, s, ky, tp, tk: batched_prefill_admit(
                    p, cfg, st, t, s, ky, tp, tk, opts, max_seq),
                donate_argnums=(1,))
        return self._prefill_batches[(bucket, k)], fresh

    # --------------------------------------------------- paged programs --
    def paged_decode(self, num_blocks: int,
                     block_size: int) -> Tuple[Callable, bool]:
        """The batched paged sampling step for one pool geometry.  Slot
        cache and pool are donated; block tables ride in as runtime
        data, so every occupancy shares this one program.
        ``opts.paged_kernel`` swaps in the block-table attention step
        (same signature, no gather-to-dense detour)."""
        key = (num_blocks, block_size)
        fresh = key not in self._paged_decodes
        if fresh:
            cfg, opts = self._cfg, self._opts
            step = (paged_kernel_sample_batched_step if opts.paged_kernel
                    else paged_sample_batched_step)
            self._paged_decodes[key] = _jit(
                "paged_decode",
                lambda p, c, pl, t, tb: step(p, cfg, c, pl, t, tb, opts),
                donate_argnums=(1, 2))
        return self._paged_decodes[key], fresh

    def paged_prefill_batch(self, bucket: int, k: int, num_blocks: int,
                            block_size: int) -> Tuple[Callable, bool]:
        """Burst admission into the paged cache for ``(prompt bucket,
        k-bucket)``: KV rows scatter into destination blocks, non-KV
        leaves + sampling into slots (slot cache and pool donated)."""
        key = (bucket, k, num_blocks, block_size)
        fresh = key not in self._paged_prefill_batches
        if fresh:
            cfg, opts = self._cfg, self._opts
            self._paged_prefill_batches[key] = _jit(
                "paged_prefill",
                lambda p, st, pl, t, s, ky, tp, tk, db: paged_prefill_admit(
                    p, cfg, st, pl, t, s, ky, tp, tk, db, opts),
                donate_argnums=(1, 2))
        return self._paged_prefill_batches[key], fresh

    def paged_admit(self) -> Tuple[Callable, bool]:
        """``admit_slot`` over the paged (KV-less) slot cache: writes one
        request's non-KV leaves plus sampling state (thaw and
        prefix-reuse admissions; stacked side donated)."""
        fresh = "admit" not in self._paged_admit
        if fresh:
            self._paged_admit["admit"] = _jit(
                "paged_admit",
                lambda st, c, i, k, t, tk: admit_slot(st, c, i, k, t, tk),
                donate_argnums=(0,))
        return self._paged_admit["admit"], fresh

    def thaw_scatter(self, nblk: int, num_blocks: int,
                     block_size: int) -> Tuple[Callable, bool]:
        """Writes ``nblk`` densified thawed KV blocks into the (donated)
        pool; keyed on the block count so thaws of similar depth share
        programs (callers bucket ``nblk`` via the prompt buckets)."""
        key = (nblk, num_blocks, block_size)
        fresh = key not in self._thaw_scatters
        if fresh:
            self._thaw_scatters[key] = _jit(
                "thaw_scatter",
                lambda pl, rk, rv, ids: paged_thaw_write(pl, rk, rv, ids),
                donate_argnums=(0,))
        return self._thaw_scatters[key], fresh

    def copy_block(self, num_blocks: int,
                   block_size: int) -> Tuple[Callable, bool]:
        """Copy-on-write block duplication (src/dst traced; pool
        donated) — one program per pool geometry."""
        key = (num_blocks, block_size)
        fresh = key not in self._copy_blocks
        if fresh:
            self._copy_blocks[key] = _jit(
                "copy_block", lambda pl, s, d: paged_copy_block(pl, s, d),
                donate_argnums=(0,))
        return self._copy_blocks[key], fresh


class CompileCache:
    """Shares :class:`ServePrograms` across engines.  Thread-hostile like
    the rest of the serving layer (one engine loop per process)."""

    def __init__(self):
        self._entries: Dict[Key, ServePrograms] = {}
        self.hits = 0
        self.misses = 0

    def entry_for(self, cfg: ModelConfig, opts: RuntimeOptions, slots: int,
                  max_seq: int, domain: str = ""
                  ) -> Tuple[ServePrograms, bool]:
        key: Key = (cfg, opts, slots, max_seq, domain)
        entry = self._entries.get(key)
        if entry is not None:
            self.hits += 1
            return entry, False
        self.misses += 1
        entry = ServePrograms(cfg, opts, max_seq)
        self._entries[key] = entry
        return entry, True

    def __len__(self) -> int:
        return len(self._entries)


# Engines that aren't handed an explicit cache share this one, so two
# engines in one process never compile the same program twice.
GLOBAL_COMPILE_CACHE = CompileCache()
