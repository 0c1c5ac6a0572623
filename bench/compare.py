"""The numbers that decide ``correct``, and the statistics of a window."""
from __future__ import annotations

import math

import numpy as np

#: A leaf whose reference gradient norm is under this share of the median
#: leaf's moves by round-off alone; it is left out of the change compared.
STILL_LEAF = 1e-3


def leaf_norms(tree) -> np.ndarray:
    """The L2 norm of every leaf of ``tree``, in tree order, as float64."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree.leaves(t)]))(tree)
    return np.asarray(norms, np.float64)


def change_norms(after, before) -> np.ndarray:
    """The L2 norm of each leaf's change from ``before`` to ``after``."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda a, b: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]))(
            after, before)
    return np.asarray(norms, np.float64)


def leaf_gaps(got: np.ndarray, want: np.ndarray, keep=None) -> np.ndarray:
    """Each kept leaf's gap between two norms: ``|got - want|`` over the
    larger of the reference's norm of that leaf and of the median leaf."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if keep is None:
        keep = np.ones(want.shape, bool)
    if not keep.any():
        raise ValueError("no leaf to compare")
    floor = float(np.median(want[keep]))
    return (np.abs(got - want) / np.maximum(want, floor))[keep]


def norm_gap(got: np.ndarray, want: np.ndarray, keep=None) -> float:
    """The worst leaf's gap between two norms (``leaf_gaps``)."""
    return float(np.max(leaf_gaps(got, want, keep)))


def moving_leaves(ref_grad_norms: np.ndarray) -> np.ndarray:
    """Leaves the reference moves by more than round-off: gradient norm at
    least ``STILL_LEAF`` of the median leaf's."""
    return ref_grad_norms >= STILL_LEAF * np.median(ref_grad_norms)


def train_numbers(prog: dict, ref: dict) -> dict:
    """The numbers of a training cell: ``loss_gap`` (relative, the worst
    of the first steps), ``grad_norm_gap`` (first gradient as the optimizer
    took it, worst leaf) and ``grad_norm_gap_median`` (its median leaf),
    and ``change_norm_gap`` (change of the parameters over the first steps,
    worst leaf, still leaves left out)."""
    losses = np.asarray(prog["losses"], np.float64)
    want = np.asarray(ref["losses"], np.float64)
    grad = leaf_gaps(prog["grad"], ref["grad"])
    return {
        "loss_gap": float(np.max(np.abs(losses - want) / np.abs(want))),
        "grad_norm_gap": float(np.max(grad)),
        "grad_norm_gap_median": float(np.median(grad)),
        "change_norm_gap": norm_gap(prog["change"], ref["change"],
                                    moving_leaves(ref["grad"])),
    }


def served_numbers(gaps: list) -> dict:
    """The numbers of a serving cell, over every served position of the
    requests checked (``gaps``: one array per request): the widest gap
    by which a served token's reference logit lies below the reference's
    best, and the mean gap."""
    flat = np.concatenate([np.asarray(g, np.float64) for g in gaps])
    return {"served_logit_gap": float(flat.max()),
            "served_logit_gap_mean": float(flat.mean())}


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by the nearest-rank method: the
    smallest value with at least ``q`` percent of the values at or below
    it."""
    if not len(values):
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100 * len(ordered)))
    return float(ordered[rank - 1])
