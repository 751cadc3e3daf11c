"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips (data x model).
Multi-pod: 2 pods x 256 = 512 chips with a leading pure-DP `pod` axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """`jax.make_mesh` with `Auto` axes: the dist code places its
    operands with explicit `shard_map` specs and NamedShardings, which
    is the Auto contract (jax now defaults new meshes to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, *, pod: int = 0):
    """Small mesh (requires >= data*model devices): forced CPU host
    devices in tests, the four chips of one host on a TPU."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
