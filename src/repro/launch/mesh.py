"""Production mesh builders.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries pure data parallelism across the ICI-disconnected pods
(DCN), "data" carries FSDP, "model" carries tensor/expert parallelism.

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before anything else).
"""
from __future__ import annotations

import jax
import numpy as np


def make_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis Auto.  The installed JAX makes
    Explicit axes by default, and ``with_sharding_constraint`` refuses a
    spec naming an Explicit axis — which the model's sequence- and
    FFN-sharding constraints do."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, devices=devices, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)}; the "
            f"dry-run must set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count=512 before importing jax")
    return make_mesh(shape, axes, devices[:n])


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices exist (tests on 1-8 CPUs)."""
    devices = jax.devices()[: data * model]
    return make_mesh((data, model), ("data", "model"), devices)


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
