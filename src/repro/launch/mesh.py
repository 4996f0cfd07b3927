"""Production mesh construction (functions only — importing this module
never touches jax device state; the dry-run sets the host-device-count
XLA flag *before* any jax import)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single v5e pod; 2x16x16 (pod, data, model)
    for the two-pod 512-chip dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests use small ones, e.g. (2, 4)).

    Axes are ``Auto``: the LM stack places arrays with
    ``with_sharding_constraint``, which ``jax.make_mesh``'s default
    ``Explicit`` axes reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Single-device mesh for CPU smoke paths."""
    return make_mesh((1, 1), ("data", "model"))
