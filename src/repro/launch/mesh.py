"""Mesh construction.

All functions (never module-level constants) so importing this module never
touches jax device state — a process that fakes a CPU mesh must set
XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax


def split_model_axis(shape, axes, node_size: int):
    """Split the "model" extent into ("node", "model") for a two-level
    interconnect (DESIGN.md §10).

    A TP group spanning multiple nodes becomes node-major: the "node" axis
    extent is the number of nodes (model_extent / node_size) and the inner
    "model" extent is node_size, so the flattened rank order — and every
    gather's concatenation order — matches the flat mesh exactly. A TP
    group that fits inside one node (node_size >= extent), or whose extent
    node_size does not divide, is left flat (the single-level schedule)."""
    shape, axes = tuple(shape), tuple(axes)
    if "model" not in axes or node_size < 1:
        return shape, axes
    i = axes.index("model")
    m = shape[i]
    if node_size >= m or m % node_size != 0:
        return shape, axes
    return (
        shape[:i] + (m // node_size, node_size) + shape[i + 1:],
        axes[:i] + ("node", "model") + axes[i + 1:],
    )


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/examples (e.g. (1, 1) on one CPU)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )
