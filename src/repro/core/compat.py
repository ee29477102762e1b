"""Mesh and named-axis helpers shared by the model, comms and launch code.

* ``AxisPair`` — a node-factored mesh axis that the collectives in
  :mod:`repro.core.comms` dispatch on.
* ``make_mesh`` — ``jax.make_mesh`` with ``Auto`` axis types: the step
  functions run under ``shard_map`` and place nothing explicitly.
* ``axis_size``/``axis_index`` — a tuple of axis names (an ``AxisPair``
  among them) gives the joint size and the outer-major linear index,
  matching mesh construction order.
"""

from __future__ import annotations

import typing

import jax
from jax import lax


class AxisPair(typing.NamedTuple):
    """A node-factored mesh axis: ``(outer, inner)`` sub-axis names.

    ``outer`` enumerates nodes (slow inter-node links), ``inner`` the ranks
    inside one node (fast intra-node links); the joint axis is linearized
    outer-major, matching mesh construction order.  Because ``AxisPair`` IS
    a tuple, it can be passed anywhere a flat tuple of axis names is
    accepted (``PartitionSpec`` entries, ``lax.psum``/``lax.pmax`` etc.) and
    behaves as the joint axis.  The collectives in :mod:`repro.core.comms`
    additionally *dispatch* on it: an ``AxisPair`` axis routes through the
    hierarchical two-level decomposition with per-level codecs, while a
    plain tuple keeps the stock single-stage collective over the joint
    axis.  Resolution from logical axis names lives in
    ``launch.mesh.comm_axes`` and ``models.params.MeshInfo.tp_axes``."""

    outer: str
    inner: str


def make_mesh(shape, axes, *, devices=None):
    """jax.make_mesh with every axis typed ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def axis_size(axis) -> int:
    """Size of a named axis; tuples (incl. AxisPair) give the joint size."""
    if isinstance(axis, (tuple, list)):
        n = 1
        for ax in axis:
            n *= axis_size(ax)
        return n
    return lax.axis_size(axis)


def axis_index(axis):
    """Rank along a named axis; tuples give the linearized joint index
    (outer-major, matching AxisPair and mesh construction order)."""
    if isinstance(axis, (tuple, list)):
        idx = None
        for ax in axis:
            i = lax.axis_index(ax)
            idx = i if idx is None else idx * axis_size(ax) + i
        return idx
    return lax.axis_index(axis)
