"""ESTMM — expert-specific transposed matmul (standalone Pallas TPU kernel).

dW[e] = sum_{rows i in e} x1[i]^T x2[i] (paper Fig. 4(d)). Production uses
the fused ESFK kernel (which adds the ESS output for free); this standalone
version exists for the unfused ablation (paper Fig. 12) and kernel tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import pallas_interpret_default


def _estmm_kernel(block_expert, x1_ref, x2_ref, o_ref, acc_ref):
    m = pl.program_id(2)
    nm = pl.num_programs(2)
    cur = block_expert[m]
    prev = jnp.where(m == 0, -1, block_expert[jnp.maximum(m - 1, 0)])
    nxt = jnp.where(m == nm - 1, -1, block_expert[jnp.minimum(m + 1, nm - 1)])

    @pl.when(cur != prev)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x1_ref[...],
        x2_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(cur != nxt)
    def _done():
        o_ref[...] = acc_ref[...][None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "b1", "b2", "interpret"))
def estmm_pallas(
    x1: jax.Array,
    x2: jax.Array,
    block_expert: jax.Array,
    counts: jax.Array,
    *,
    bm: int = 128,
    b1: int = 128,
    b2: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """(Np, D1), (Np, D2) sorted rows -> (E, D1, D2) grads (f32)."""
    if interpret is None:
        interpret = pallas_interpret_default()
    np_rows, d1 = x1.shape
    _, d2 = x2.shape
    e = counts.shape[0]
    bm = min(bm, np_rows)
    b1 = min(b1, d1)
    b2 = min(b2, d2)
    assert np_rows % bm == 0 and d1 % b1 == 0 and d2 % b2 == 0
    assert block_expert.shape[0] * bm == np_rows
    grid = (d1 // b1, d2 // b2, np_rows // bm)

    out = pl.pallas_call(
        _estmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, b1), lambda i, j, m, be: (m, i)),
                pl.BlockSpec((bm, b2), lambda i, j, m, be: (m, j)),
            ],
            out_specs=pl.BlockSpec(
                (1, b1, b2), lambda i, j, m, be: (be[m], i, j)
            ),
            scratch_shapes=[pltpu.VMEM((b1, b2), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, d1, d2), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * np_rows * d1 * d2,
            bytes_accessed=(
                (d2 // b2) * x1.size * x1.dtype.itemsize
                + (d1 // b1) * x2.size * x2.dtype.itemsize
                + e * d1 * d2 * 4
            ),
            transcendentals=0,
        ),
        interpret=interpret,
    )(block_expert, x1, x2)
    return jnp.where((counts > 0)[:, None, None], out, 0.0)
