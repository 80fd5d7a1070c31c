"""Production mesh factory.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state. The dry-run process (launch/dryrun.py) forces 512 host
placeholder devices *before* any jax import; ordinary processes see one CPU.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """`jax.make_mesh` with Auto axes, which is what every caller here
    wants."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16 data x 16 model). Multi-pod: 2 x 256 with a
    leading `pod` axis that composes with `data` for batch parallelism (the
    gradient all-reduce is the only cross-pod collective in steady state)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh for unit tests on the real device set."""
    return _mesh(shape, axes)
