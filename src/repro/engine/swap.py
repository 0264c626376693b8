"""Model-adaptive memory swapping (paper §III-C2 ❽).

On mobile the paper swaps activations between GPU and CPU memory; the TPU
analogue is HBM ↔ host offload.  JAX exposes this through sharding memory
kinds ("device" vs "pinned_host"), which both the TPU and the CPU
backend provide.  With ``use_memory_kinds`` off the transfer is only
*modeled*; either way the Swapper tracks bytes moved and charges them at
the host-link bandwidth so the middleware optimizer sees honest costs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

HOST_LINK_BW = 32e9   # bytes/s PCIe-class host link (v5e host DMA)


def _to_memory(x: jax.Array, kind: str) -> jax.Array:
    """Move ``x`` to memory ``kind`` of its own device.  A failed transfer
    raises: keeping the array where it was would report an offload that
    never happened."""
    dev = x.devices().pop()
    return jax.device_put(x, jax.sharding.SingleDeviceSharding(
        dev, memory_kind=kind))


@dataclass
class SwapRecord:
    name: str
    bytes: int
    direction: str   # "out" (to host) | "in" (to device)


@dataclass
class Swapper:
    """Tracks (and with ``use_memory_kinds``, performs) HBM<->host
    transfers."""
    use_memory_kinds: bool = False      # real host offload
    records: List[SwapRecord] = field(default_factory=list)
    resident_host: Dict[str, Any] = field(default_factory=dict)

    def offload(self, name: str, x: jax.Array) -> jax.Array:
        self.records.append(SwapRecord(name, x.size * x.dtype.itemsize, "out"))
        if self.use_memory_kinds:
            x = _to_memory(x, "pinned_host")
        self.resident_host[name] = x
        return x

    def fetch(self, name: str) -> jax.Array:
        x = self.resident_host.pop(name)
        self.records.append(SwapRecord(name, x.size * x.dtype.itemsize, "in"))
        if self.use_memory_kinds:
            x = _to_memory(x, "device")
        return x

    def total_bytes(self) -> int:
        return sum(r.bytes for r in self.records)

    def transfer_seconds(self, link_bw: float = HOST_LINK_BW) -> float:
        return self.total_bytes() / link_bw


def swap_plan(act_bytes_per_layer: List[int], budget_bytes: float
              ) -> Tuple[List[int], int]:
    """Choose which layers' saved activations to host-offload.

    DL inference is sequential (the paper's observation), so activations
    needed latest in the backward pass (earliest layers) are the best swap
    candidates: they have the longest idle window to prefetch back.
    Returns (layer indices to swap, resident bytes after swapping)."""
    total = sum(act_bytes_per_layer)
    swapped: List[int] = []
    resident = total
    for i, b in enumerate(act_bytes_per_layer):      # earliest first
        if resident <= budget_bytes:
            break
        swapped.append(i)
        resident -= b
    return swapped, int(resident)


def swap_overlap_latency(swapped_bytes: int, compute_seconds: float,
                         link_bw: float = HOST_LINK_BW) -> float:
    """Exposed (non-overlapped) transfer time: transfers hide under compute
    when the sequential window allows; only the excess is charged."""
    xfer = swapped_bytes / link_bw
    return max(0.0, xfer - compute_seconds)
