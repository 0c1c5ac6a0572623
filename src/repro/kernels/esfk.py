"""ESFK — expert-specific fused backward kernel (Pallas TPU).

Computes weight grads (ESTMM) and bias grads (ESS) in ONE pass over the
upstream-gradient tiles:

  dW[e] = sum_{rows i in e} x1[i]^T x2[i]        (paper Fig. 4(d))
  db[e] = sum_{rows i in e} x2[i]                (paper Fig. 4(c))

Adaptation note (DESIGN.md §2): the paper fuses ESS+ESTMM+ESMM by
concatenating CUDA thread grids to raise SM occupancy. On TPU the profitable
fusion is HBM-traffic fusion — x2 (= dy) is read once for both outputs.
dX remains a separate ESMM (different output layout, MXU-bound anyway).

Grid is (d1_blocks, d2_blocks, m_blocks) with m innermost so that revisits of
the accumulator output block (one per expert) are consecutive — the sorted
layout guarantees equal experts occupy consecutive m blocks. The dW blocks
default to ``esfk_blocks``: the whole ``(D1, D2)`` expert where it fits
VMEM, so the grid is ``(1, 1, m_blocks)``, x1 and x2 are each read once and
each expert's dW is written back once.

The db output carries one junk row (shape (E+1, 1, D2), the unit axis
keeping its (1, 1, BLK_D2) tile within the (8, 128) block rule): for
d1-block index i > 0 the kernel parks its write target on row E so the auto
copy-back of the revisited buffer never corrupts real rows. Caller slices
[:E].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import pallas_interpret_default
from repro.kernels import tiles


def _esfk_kernel(
    block_expert,  # (num_m_blocks,) scalar prefetch
    x1_ref,        # (BLK_M, BLK_D1)
    x2_ref,        # (BLK_M, BLK_D2)
    dw_ref,        # (1, BLK_D1, BLK_D2)
    db_ref,        # (1, 1, BLK_D2)
    acc_dw,        # VMEM (BLK_D1, BLK_D2) f32
    acc_db,        # VMEM (1, BLK_D2) f32
):
    i = pl.program_id(0)
    m = pl.program_id(2)
    nm = pl.num_programs(2)

    cur = block_expert[m]
    prev = jnp.where(m == 0, -1, block_expert[jnp.maximum(m - 1, 0)])
    nxt = jnp.where(
        m == nm - 1, -1, block_expert[jnp.minimum(m + 1, nm - 1)]
    )
    is_first = cur != prev
    is_last = cur != nxt

    @pl.when(is_first)
    def _init():
        acc_dw[...] = jnp.zeros_like(acc_dw)
        acc_db[...] = jnp.zeros_like(acc_db)

    acc_dw[...] += jax.lax.dot_general(
        x1_ref[...],
        x2_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),  # x1^T @ x2
        preferred_element_type=jnp.float32,
    )
    # db accumulation costs BLK_M*BLK_D2 adds — negligible next to the
    # BLK_M*BLK_D1*BLK_D2 MACs above; keeping it unconditional keeps the
    # revisit/write logic uniform.
    acc_db[...] += jnp.sum(
        x2_ref[...].astype(jnp.float32), axis=0, keepdims=True
    )

    @pl.when(is_last)
    def _done():
        dw_ref[...] = acc_dw[...][None].astype(dw_ref.dtype)
        db_ref[0] = acc_db[...].astype(db_ref.dtype)


def esfk_blocks(
    bm: int,
    d1: int,
    d2: int,
    *,
    itemsize1: int,
    itemsize2: int,
) -> tuple[int, int]:
    """``(b1, b2)`` of an esfk call with row block ``bm`` over x1
    ``(Np, d1)`` and x2 ``(Np, d2)``: the whole expert's dW, ``(d1, d2)``,
    where it fits the VMEM budget (module ``tiles``)."""
    return tiles.choose(
        (d1, d2),
        lambda b: esfk_vmem(bm, *b, itemsize1=itemsize1,
                            itemsize2=itemsize2))


def esfk_vmem(bm, b1, b2, *, itemsize1, itemsize2) -> int:
    """VMEM footprint of one esfk call at blocks ``(bm, b1, b2)``."""
    f32 = 4
    blocks = [((bm, b1), itemsize1), ((bm, b2), itemsize2),
              ((b1, b2), f32), ((1, b2), f32)]
    # The two accumulators, the dot's result and x2 widened for db.
    scratch = [((b1, b2), f32), ((1, b2), f32), ((b1, b2), f32),
               ((bm, b2), f32)]
    return tiles.vmem_bytes(blocks, scratch)


@functools.partial(
    jax.jit, static_argnames=("bm", "b1", "b2", "interpret")
)
def esfk_pallas(
    x1: jax.Array,
    x2: jax.Array,
    block_expert: jax.Array,
    counts: jax.Array,
    num_experts: int | None = None,
    *,
    bm: int = 128,
    b1: int | None = None,
    b2: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused (dW, db) over the sorted layout.

    x1: (Np, D1) saved activations; x2: (Np, D2) upstream grads;
    block_expert: (Np // bm,); counts: (E,) true rows per expert (used to
    zero experts that received no tokens — their output blocks are never
    visited by the grid).
    Returns dW: (E, D1, D2) f32, db: (E, D2) f32.
    """
    if interpret is None:
        interpret = pallas_interpret_default()
    np_rows, d1 = x1.shape
    np2, d2 = x2.shape
    assert np_rows == np2
    e = counts.shape[0] if num_experts is None else num_experts
    bm = min(bm, np_rows)
    i1, i2 = x1.dtype.itemsize, x2.dtype.itemsize
    auto_b1, auto_b2 = esfk_blocks(bm, d1, d2, itemsize1=i1, itemsize2=i2)
    b1 = min(b1 or auto_b1, d1)
    b2 = min(b2 or auto_b2, d2)
    assert np_rows % bm == 0 and d1 % b1 == 0 and d2 % b2 == 0
    assert block_expert.shape[0] * bm == np_rows
    grid = (d1 // b1, d2 // b2, np_rows // bm)

    flops = 2 * np_rows * d1 * d2
    bytes_accessed = (
        (d2 // b2) * x1.size * x1.dtype.itemsize
        + (d1 // b1) * x2.size * x2.dtype.itemsize
        + e * d1 * d2 * 4
    )
    vmem = esfk_vmem(bm, b1, b2, itemsize1=i1, itemsize2=i2)

    dw, db_full = pl.pallas_call(
        _esfk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, b1), lambda i, j, m, be: (m, i)),
                pl.BlockSpec((bm, b2), lambda i, j, m, be: (m, j)),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, b1, b2), lambda i, j, m, be: (be[m], i, j)
                ),
                # Junk-row parking for i > 0 (see module docstring).
                pl.BlockSpec(
                    (1, 1, b2),
                    lambda i, j, m, be: (jnp.where(i == 0, be[m], e), 0, j),
                ),
            ],
            scratch_shapes=[
                pltpu.VMEM((b1, b2), jnp.float32),
                pltpu.VMEM((1, b2), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((e, d1, d2), jnp.float32),
            jax.ShapeDtypeStruct((e + 1, 1, d2), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=tiles.vmem_limit(vmem),
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops, bytes_accessed=bytes_accessed, transcendentals=0
        ),
        interpret=interpret,
    )(block_expert, x1, x2)

    # Experts with zero routed tokens are never visited by the grid: their
    # HBM output blocks are undefined. Mask them to exact zeros.
    has = counts > 0
    dw = jnp.where(has[:, None, None], dw, 0.0)
    db = jnp.where(has[:, None], db_full[:e, 0], 0.0)
    return dw, db
