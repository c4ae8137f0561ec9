"""Every device mesh of the repo is built here.

Functions (not module constants), so importing this module never touches
jax device state.  Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model) — `pod` carries only
DCN-friendly gradient/statistics reductions; FSDP all-gathers stay on the
in-pod ICI `data` axis.

All axes are ``AxisType.Auto``: the model places its arrays with
``NamedSharding`` and ``with_sharding_constraint`` and lets the compiler
propagate the rest.  ``jax.make_mesh`` otherwise gives explicit axes, under
which an ordinary gather (the embedding lookup) refuses to pick an output
sharding.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, devices=None) -> Mesh:
    """A mesh of ``shape`` over ``devices`` (default: all), Auto axes."""
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto)
    return Mesh(np.asarray(devices).reshape(shape), tuple(axes),
                axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh() -> Mesh:
    """Whatever devices exist, as a (data=n, model=1) mesh."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))
