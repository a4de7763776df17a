"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required: smoke tests see 1 device; only
dryrun.py forces 512).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_small_mesh(data: int = 1, model: int = 1):
    """Mesh for tests/examples on whatever devices exist."""
    return _make_mesh((data, model), ("data", "model"))
