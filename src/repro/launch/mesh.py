"""Production mesh definition (kept as functions — importing this module
never touches jax device state).

Meshes use ``Auto`` axis types: jax 0.9 makes ``jax.make_mesh`` default to
``Explicit`` axes, which the partitioning rules here do not use."""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e target: 16x16 = 256 chips per pod; 2 pods multi-pod.

    Axes: ``data`` (batch / FSDP) x ``model`` (tensor parallel), plus a
    leading ``pod`` axis in the multi-pod configuration (DCN-connected).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small CPU meshes, e.g. (2, 4))."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )
