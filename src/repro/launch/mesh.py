"""Production meshes.

A function (not a module constant) so importing never touches jax device
state. Single-pod: 16×16 = 256 chips ("data", "model"); multi-pod: 2×16×16 =
512 chips ("pod", "data", "model") — the pod axis is data-parallel across
the inter-pod (DCN/ICI) links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """1×1 mesh on the local device — smoke tests and examples."""
    return _make_mesh((1, 1), ("data", "model"))
