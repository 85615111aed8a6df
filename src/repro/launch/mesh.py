"""Production mesh construction (DESIGN.md §6, system-prompt contract).

A FUNCTION, not a module constant: importing this module never touches jax
device state (jax locks the device count at first backend init, and tests
must see 1 CPU device while the dry-run sees 512 host devices).

Axes are `AxisType.Auto`: sharding is propagated by the compiler and a
`with_sharding_constraint` is a hint. (jax.make_mesh's default, `Explicit`
axes, turns such a constraint into an assertion on the operand's sharding
type.) Activate a mesh with `jax.set_mesh(mesh)`.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return _mesh((1, 1), ("data", "model"))
