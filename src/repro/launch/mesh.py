"""Production mesh factories.

Defined as functions (not module constants) so importing never touches jax
device state — the dry-run entry point must set XLA_FLAGS before any jax
device query.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh over ``jax.devices()`` with Auto (GSPMD-propagated)
    axis types (reduced-device tests use (2,2,2) / (2,4) etc.)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """The batch-parallel axes for this mesh (pod folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
