"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU smoke tests."""
    return make_mesh((1, 1), ("data", "model"))


def make_test_mesh(data: int = 4, model: int = 2):
    """Small mesh for unit tests (needs XLA_FLAGS device count)."""
    return make_mesh((data, model), ("data", "model"))
