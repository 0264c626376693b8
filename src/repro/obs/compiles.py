"""Backend compiles, counted against the engine tick that caused them.

``ServeStats.recompiles`` counts misses of the engine's program cache;
an eager op that compiles, or a program that jax itself re-lowers, is
not among them.  XLA's own compiles are seen through one process-wide
``jax.monitoring`` listener on ``/jax/core/compile/backend_compile_duration``:
each one adds to the counter routed to it by the open
:class:`counting` block (the engine opens one around each tick), and
counts nowhere when none is open.  jax times compile-or-load as one
event, so a program loaded from the persistent compilation cache counts
too: the counter reads programs built inside the ticks.
"""
from __future__ import annotations

from typing import Optional

import jax

from .metrics import Counter

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_open: Optional[Counter] = None


def _on_duration(event: str, secs: float, **_) -> None:
    if event == BACKEND_COMPILE and _open is not None:
        _open.inc()


jax.monitoring.register_event_duration_secs_listener(_on_duration)


class counting:
    """Route the process's backend compiles to ``counter`` inside the
    block; blocks nest, the innermost counting (one engine loop per
    process, like the rest of the serving layer)."""

    __slots__ = ("counter", "_outer")

    def __init__(self, counter: Counter):
        self.counter = counter

    def __enter__(self) -> Counter:
        global _open
        self._outer, _open = _open, self.counter
        return self.counter

    def __exit__(self, *exc) -> None:
        global _open
        _open = self._outer
