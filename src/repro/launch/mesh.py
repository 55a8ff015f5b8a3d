"""Production meshes. Functions (not module constants) so importing this
module never touches jax device state."""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/benchmarks (e.g. (4, 2) on 8 CPU devices)."""
    return _mk(tuple(shape), tuple(axes))


def abstract_mesh(shape, axes):
    """AbstractMesh: no devices needed, spec-validity checks only (used by
    tests against the production mesh shapes)."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))
