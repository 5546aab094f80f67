"""Production meshes.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
device query).
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType

__all__ = ["make_production_mesh", "batch_axes", "CHIPS_PER_POD"]

CHIPS_PER_POD = 256


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh():
    """Whatever devices exist (1 on the CPU container), same axis names."""
    n = jax.device_count()
    return jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over (DP): pod + data."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
