"""ESMM — expert-specific matrix multiplication (Pallas TPU kernel).

Grouped matmul over the expert-sorted layout: every BLK_M-row block of ``xs``
belongs to one expert (``block_expert``, scalar-prefetched so Mosaic can
schedule the weight DMA for block i+1 while block i is on the MXU). The
weight block defaults to the whole expert where it fits VMEM
(``esmm_blocks``): its index then holds over the expert's consecutive row
blocks and the weights are fetched once per run of them.

  ys[i] = xs[i] @ W[e(i)] (+ b[e(i)])          (paper Fig. 4(b))

Adaptation from the paper's CUDA kernel: the per-thread-block gather through
the re-index vector becomes a single ahead-of-time sort-permute (see
``core.reindex``); the kernel itself then streams contiguous VMEM tiles into
the MXU with a float32 accumulator, which is the TPU-native shape of the same
zero-redundancy computation.

Quantized weights (DESIGN.md §8): with ``w_scales`` the weight operand is an
int8/fp8 payload whose block-wise scales (``quant.core.quantize_blockwise``)
ride along as a congruent BlockSpec — each weight tile is dequantized in
VMEM right before the MXU contraction, so only the quantized bytes cross
HBM (the cost estimate reflects the smaller itemsize automatically).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import pallas_interpret_default
from repro.kernels import tiles
from repro.quant.core import dequant_tile, tile_scales


def _esmm_kernel(
    block_expert,  # scalar-prefetch (num_blocks,)
    x_ref,         # (BLK_M, BLK_K)
    w_ref,         # (1, BLK_K, BLK_N) or (1, BLK_N, BLK_K) if transposed
    *rest,
    transpose: bool,
    has_scale: bool,
    has_bias: bool,
):
    rest = list(rest)
    s_ref = rest.pop(0) if has_scale else None
    b_ref = rest.pop(0) if has_bias else None
    o_ref, acc_ref = rest
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        if b_ref is None:
            acc_ref[...] = jnp.zeros_like(acc_ref)
        else:
            acc_ref[...] = jnp.broadcast_to(
                b_ref[0].astype(jnp.float32), acc_ref.shape
            )

    w = w_ref[0]
    if has_scale:
        # VMEM dequant right before the contraction (DESIGN.md §8).
        w = dequant_tile(w, s_ref[0, 0, 0])
    # transposed: w block is (BLK_N, BLK_K); contract x dim 1 with w dim 1.
    dims = (((1,), (1,)), ((), ())) if transpose else (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w, dimension_numbers=dims,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def esmm_blocks(
    bm: int,
    d1: int,
    d2: int,
    *,
    x_itemsize: int,
    w_itemsize: int,
    quant_tiles: tuple[int, int] | None = None,
    has_bias: bool = False,
) -> tuple[int, int]:
    """``(bk, bn)`` of an esmm call with row block ``bm`` over
    ``(Np, d1) x (d1, d2)``: the whole expert, ``(d1, d2)``, where it fits
    the VMEM budget (module ``tiles``). ``quant_tiles``: a quantized weight's
    tile along d1 and d2, which must divide the blocks."""
    return tiles.choose(
        (d1, d2),
        lambda b: esmm_vmem(bm, *b, x_itemsize=x_itemsize,
                            w_itemsize=w_itemsize, quant_tiles=quant_tiles,
                            has_bias=has_bias),
        tiles=quant_tiles)


def esmm_vmem(bm, bk, bn, *, x_itemsize, w_itemsize, quant_tiles=None,
              has_bias=False) -> int:
    """VMEM footprint of one esmm call at blocks ``(bm, bk, bn)``."""
    f32 = 4
    blocks = [((bm, bk), x_itemsize), ((bk, bn), w_itemsize),
              ((bm, bn), x_itemsize)]
    if has_bias:
        blocks.append(((1, bn), x_itemsize))
    # The accumulator and the dot's result; a quantized tile is widened
    # to f32 beside its broadcast scales.
    scratch = [((bm, bn), f32)] * 2
    if quant_tiles is not None:
        blocks.append(((bk // quant_tiles[0], bn // quant_tiles[1]), f32))
        scratch += [((bk, bn), f32)] * 2
    return tiles.vmem_bytes(blocks, scratch)


@functools.partial(
    jax.jit,
    static_argnames=("transpose_rhs", "bm", "bn", "bk", "interpret"),
)
def esmm_pallas(
    xs: jax.Array,
    w: jax.Array,
    b: jax.Array | None,
    block_expert: jax.Array,
    *,
    w_scales: Optional[jax.Array] = None,
    transpose_rhs: bool = False,
    bm: int = 128,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Grouped matmul ys = xs @ W[e] (+ b[e]) on the sorted layout.

    xs: (Np, D1); w: (E, D1, D2) ((E, D2, D1) when transpose_rhs);
    b: (E, D2) or None; block_expert: (Np // bm,). ``w_scales``
    (E, n1, n2): block-wise scales of a quantized ``w`` (same axis order
    as w) — dequantized tile-wise in VMEM before the MXU contraction.
    ``bn``/``bk`` default to ``esmm_blocks``: the whole expert where it
    fits VMEM, so its weights cross HBM once per run of its row blocks.
    """
    if interpret is None:
        interpret = pallas_interpret_default()
    np_rows, d1 = xs.shape
    if transpose_rhs:
        e, d2, d1w = w.shape
    else:
        e, d1w, d2 = w.shape
    assert d1w == d1, (w.shape, xs.shape)
    bm = min(bm, np_rows)
    quant_tiles = None
    if w_scales is not None:
        n1, n2 = w_scales.shape[1:][::-1] if transpose_rhs \
            else w_scales.shape[1:]
        quant_tiles = (d1 // n1, d2 // n2)
    auto_bk, auto_bn = esmm_blocks(
        bm, d1, d2, x_itemsize=xs.dtype.itemsize,
        w_itemsize=w.dtype.itemsize, quant_tiles=quant_tiles,
        has_bias=b is not None)
    bn = min(bn or auto_bn, d2)
    bk = min(bk or auto_bk, d1)
    assert np_rows % bm == 0 and d2 % bn == 0 and d1 % bk == 0, (
        f"shapes ({np_rows},{d1})x({d2}) not divisible by blocks {bm, bn, bk}"
    )
    assert block_expert.shape[0] * bm == np_rows, (
        "block_expert must be built with blk == bm"
    )
    grid = (np_rows // bm, d2 // bn, d1 // bk)

    if transpose_rhs:
        w_spec = pl.BlockSpec((1, bn, bk), lambda i, j, k, be: (be[i], j, k))
    else:
        w_spec = pl.BlockSpec((1, bk, bn), lambda i, j, k, be: (be[i], k, j))

    kernel = functools.partial(
        _esmm_kernel, transpose=transpose_rhs,
        has_scale=w_scales is not None, has_bias=b is not None,
    )
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k, be: (i, k)),
        w_spec,
    ]
    args = [block_expert, xs, w]
    if w_scales is not None:
        assert w_scales.shape[0] == e, (w_scales.shape, w.shape)
        if transpose_rhs:
            st = tile_scales(w_scales, (d2, d1), (bn, bk))
            s_map = lambda i, j, k, be: (be[i], j, k, 0, 0)  # noqa: E731
        else:
            st = tile_scales(w_scales, (d1, d2), (bk, bn))
            s_map = lambda i, j, k, be: (be[i], k, j, 0, 0)  # noqa: E731
        in_specs.append(pl.BlockSpec((1, 1, 1) + st.shape[3:], s_map))
        args.append(st)
    if b is not None:
        # (E, 1, D2) so the (1, 1, bn) tile meets the (8, 128) block rule.
        in_specs.append(
            pl.BlockSpec((1, 1, bn), lambda i, j, k, be: (be[i], 0, j)))
        args.append(b.reshape(e, 1, d2))

    flops = 2 * np_rows * d1 * d2
    # A whole-expert weight block is fetched once per run of its expert's
    # row blocks (at most E runs); a split one once per row block, and
    # then xs is read once per column block unless bk spans D1.
    w_fetches = min(grid[0], e) if (bk, bn) == (d1, d2) else grid[0]
    bytes_accessed = (
        (1 if bk == d1 else grid[1]) * xs.size * xs.dtype.itemsize
        + w_fetches * d1 * d2 * w.dtype.itemsize
        + np_rows * d2 * xs.dtype.itemsize
    )
    if w_scales is not None:
        bytes_accessed += w_fetches * int(
            w_scales.shape[1] * w_scales.shape[2]) * 4
    vmem = esmm_vmem(bm, bk, bn, x_itemsize=xs.dtype.itemsize,
                     w_itemsize=w.dtype.itemsize, quant_tiles=quant_tiles,
                     has_bias=b is not None)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, be: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((np_rows, d2), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=tiles.vmem_limit(vmem),
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops, bytes_accessed=bytes_accessed, transcendentals=0
        ),
        interpret=interpret,
    )(*args)
