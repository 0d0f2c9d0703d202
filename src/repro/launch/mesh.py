"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state — the dry-run must set XLA_FLAGS
before anything initializes the backend.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ('data', 'model') single-pod; ('pod', 'data', 'model') multi-pod.
    The 'pod' axis is outer data-parallelism across the (thin) inter-pod
    links — exactly the boundary where NEURON-Fabric's low-bit gradient
    aggregation buys the most (DESIGN.md §4).
    """
    return make_mesh((2, 16, 16) if multi_pod else (16, 16))


def make_mesh(shape):
    """Mesh of auto axes: ``('data', 'model')`` for two dims,
    ``('pod', 'data', 'model')`` for three."""
    axes = ("pod", "data", "model")[-len(shape):]
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def dp_axes_of(mesh) -> tuple:
    """The data-parallel (gradient aggregation) axes of a mesh."""
    return tuple(a for a in mesh.axis_names if a != "model")
