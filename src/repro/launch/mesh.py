"""Production mesh construction (multi-pod dry-run contract).

A function, not a module-level constant — importing this module never
touches jax device state.  The dry-run entry point
(``repro/launch/dryrun.py``) sets ``XLA_FLAGS=--xla_force_host_platform_
device_count=512`` *before any jax import* so 512 placeholder devices
exist; nothing else in the repo does.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """Mesh with Auto axes: the sharding rules place arrays through
    ``NamedSharding`` specs and let the compiler propagate the rest
    (``jax.make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke paths that still exercise jit+shardings."""
    return _mesh((1, 1), ("data", "model"))
