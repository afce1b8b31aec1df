"""Production meshes. A FUNCTION, not a module constant — importing this
module never touches jax device state (required by the dry-run contract)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic restarts, tests). shape/axes like above."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def single_device_mesh():
    return make_mesh((1, 1), ("data", "model"))
