"""Production mesh definitions (TPU v5e target).

Single pod: (16, 16) = ('data', 'model') = 256 chips.
Multi-pod:  (2, 16, 16) = ('pod', 'data', 'model') = 512 chips; the 'pod'
axis is the slow DCN/ICI-bridge axis and carries only data-parallel gradient
reduction (optionally int8-compressed), never TP collectives.

A FUNCTION, not a module constant: importing this module never touches jax
device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh needs {n} devices, found {len(devices)} — the dry-run must "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Degenerate 1-device mesh for smoke tests of the sharded code path."""
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                         axis_types=(AxisType.Auto,) * 2)
