"""Pure-jnp oracles for the expert-specific operators.

These are the ground truth every other implementation (Pallas, blocked) is
tested against. They operate on the *sorted layout* produced by
``core.reindex`` and are deliberately simple (one-hot einsums); never use
them on real workloads.

Paper mapping (Fig. 3 / Table 5):
  esmm  — expert-specific matrix multiplication.
  ess   — expert-specific summation (bias grads).
  estmm — expert-specific transposed matmul (weight grads).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _row_expert(block_expert: jax.Array, blk: int) -> jax.Array:
    """Expand block->expert map to a per-row expert id."""
    return jnp.repeat(block_expert, blk)


def esmm(
    xs: jax.Array,
    w: jax.Array,
    b: jax.Array | None,
    block_expert: jax.Array,
    *,
    transpose_rhs: bool = False,
) -> jax.Array:
    """ys[i] = xs[i] @ W[e(i)] (+ b[e(i)]).

    Args:
      xs: (Np, D1) sorted tokens.
      w:  (E, D1, D2) expert weights; (E, D2, D1) when transpose_rhs.
      b:  (E, D2) or None.
      block_expert: (Np//BLK,) block->expert map.
    """
    np_rows = xs.shape[0]
    blk = np_rows // block_expert.shape[0]
    e = w.shape[0]
    re = _row_expert(block_expert, blk)
    onehot = jax.nn.one_hot(re, e, dtype=xs.dtype)  # (Np, E)
    wx = w.astype(xs.dtype)
    if transpose_rhs:
        y = jnp.einsum("nd,ne,efd->nf", xs, onehot, wx)
    else:
        y = jnp.einsum("nd,ne,edf->nf", xs, onehot, wx)
    if b is not None:
        y = y + onehot @ b.astype(xs.dtype)
    return y


def ess(dy: jax.Array, block_expert: jax.Array, num_experts: int) -> jax.Array:
    """db[e] = sum of dy rows routed to e.  dy: (Np, D) -> (E, D)."""
    np_rows = dy.shape[0]
    blk = np_rows // block_expert.shape[0]
    re = _row_expert(block_expert, blk)
    onehot = jax.nn.one_hot(re, num_experts, dtype=dy.dtype)
    return jnp.einsum("ne,nd->ed", onehot, dy)


def estmm(
    x1: jax.Array, x2: jax.Array, block_expert: jax.Array, num_experts: int
) -> jax.Array:
    """dW[e] = sum_{i in e} x1[i]^T x2[i].  (Np,D1),(Np,D2) -> (E,D1,D2)."""
    np_rows = x1.shape[0]
    blk = np_rows // block_expert.shape[0]
    re = _row_expert(block_expert, blk)
    onehot = jax.nn.one_hot(re, num_experts, dtype=x1.dtype)
    return jnp.einsum("ne,nd,nf->edf", onehot, x1, x2)


def esfk(
    x1: jax.Array, x2: jax.Array, block_expert: jax.Array, num_experts: int
) -> tuple[jax.Array, jax.Array]:
    """Fused backward: (dW, db) from one pass over x2 (= upstream grads)."""
    return (
        estmm(x1, x2, block_expert, num_experts),
        ess(x2, block_expert, num_experts),
    )


def moe_ffn_per_token(
    x: jax.Array,
    expert_idx: jax.Array,
    gates: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    b2: jax.Array,
    act,
) -> jax.Array:
    """End-to-end per-token MoE FFN oracle (no sorted layout at all).

    y[t] = sum_s gates[t,s] * (act(x[t] @ W1[e] + b1[e]) @ W2[e] + b2[e]),
    e = expert_idx[t, s].  Used to validate the whole hexa pipeline.
    """
    def token_fn(xt, et, gt):
        def slot(e):
            h = act(xt @ w1[e] + b1[e])
            return h @ w2[e] + b2[e]
        ys = jax.vmap(slot)(et)  # (k, D2)
        return jnp.sum(ys * gt[:, None].astype(ys.dtype), axis=0)

    return jax.vmap(token_fn)(x, expert_idx, gates)
