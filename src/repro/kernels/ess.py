"""ESS — expert-specific summation (standalone Pallas TPU kernel).

db[e] = sum of rows routed to expert e (paper Fig. 4(c)). The fused ESFK
kernel subsumes this in production; the standalone kernel exists for the
paper's unfused ablation (Fig. 12) and for kernel-level testing.

Grid (d_blocks, m_blocks), m innermost: revisits of the (per-expert) output
block are consecutive because the layout is expert-sorted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import pallas_interpret_default


def _ess_kernel(block_expert, x_ref, o_ref, acc_ref):
    m = pl.program_id(1)
    nm = pl.num_programs(1)
    cur = block_expert[m]
    prev = jnp.where(m == 0, -1, block_expert[jnp.maximum(m - 1, 0)])
    nxt = jnp.where(m == nm - 1, -1, block_expert[jnp.minimum(m + 1, nm - 1)])

    @pl.when(cur != prev)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.sum(
        x_ref[...].astype(jnp.float32), axis=0, keepdims=True
    )

    @pl.when(cur != nxt)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bd", "interpret"))
def ess_pallas(
    x: jax.Array,
    block_expert: jax.Array,
    counts: jax.Array,
    *,
    bm: int = 128,
    bd: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """x: (Np, D) sorted rows -> (E, D) per-expert sums (f32)."""
    if interpret is None:
        interpret = pallas_interpret_default()
    np_rows, d = x.shape
    e = counts.shape[0]
    bm = min(bm, np_rows)
    bd = min(bd, d)
    assert np_rows % bm == 0 and d % bd == 0
    assert block_expert.shape[0] * bm == np_rows
    grid = (d // bd, np_rows // bm)

    out = pl.pallas_call(
        _ess_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((bm, bd), lambda j, m, be: (m, j))],
            # (E, 1, D) output: a (1, 1, bd) tile meets the (8, 128) rule.
            out_specs=pl.BlockSpec((1, 1, bd), lambda j, m, be: (be[m], 0, j)),
            scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, 1, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=np_rows * d,
            bytes_accessed=x.size * x.dtype.itemsize + e * d * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(block_expert, x)
    return jnp.where((counts > 0)[:, None], out[:, 0], 0.0)
