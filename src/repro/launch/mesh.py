"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS for 512 host devices before any jax
import; tests and benches must keep seeing 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU tests (requires >= n_data*n_model host devices)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple:
    """Batch-sharding axes: ("pod","data") on the multi-pod mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
