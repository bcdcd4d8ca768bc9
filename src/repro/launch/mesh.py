"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod
axis extends data parallelism across the inter-pod (DCN/ICI) boundary.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh():
    """1-device mesh with production axis names (CPU smoke tests)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
