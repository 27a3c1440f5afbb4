"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — jax locks the device count on first init,
and only launch/dryrun.py is allowed to set the 512-device XLA flag.

Every axis is ``AxisType.Auto``: the sharding code (runtime/sharding.py,
serving/placement.py) places arrays and lets the partitioner propagate,
whereas ``jax.make_mesh`` builds Explicit axes by default, under which
slicing a data-sharded batch or contracting a sharded dim is a type
error.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Whatever-fits mesh for CPU smoke runs / examples."""
    n = len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))
