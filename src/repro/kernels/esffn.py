"""ESFFN — fused expert-FFN megakernel (Pallas TPU). DESIGN.md §5.

ONE ``pallas_call`` runs the whole expert FFN over the expert-sorted rows
``xs = core.reindex.gather_rows(x, row_token)``:

  up/gate ESMM -> activation -> down ESMM -> gate-weighted output

Per BLK_M block the kernel

  1. reads its ``(BLK_M, D)`` tile of the sorted rows (the caller gathers
     them with one XLA gather: a one-row DMA per token out of the unsorted
     ``(N, D)`` activations is refused by Mosaic, a single row not being
     aligned to the (8, 128) / packed (16, 128) HBM tiling),
  2. computes the up/gate projections against the scalar-prefetched expert
     weight tiles, sharing the single VMEM-resident x tile between gate and
     up in the GLU case,
  3. applies the activation on the VPU in float32,
  4. accumulates the down projection in a float32 VMEM accumulator across
     hidden-dim tiles, and
  5. writes the gate-weighted sorted output (combine-ready: the caller's
     scatter-add needs no further gate multiply).

The ``(Np, F)`` hidden activations exist only tile-wise in VMEM, so the
kernel's HBM traffic is the token rows, the expert weights and the output
— the forward analogue of the ESFK backward fusion (DESIGN.md §2): the
unfused gather/esmm/act/esmm/combine composition round-trips the hidden
through HBM between stages. The hidden block defaults to ``esffn_blocks``:
all of F where the expert's weights fit VMEM whole, so they are fetched
once per run of the expert's consecutive row blocks, not once per step.

Padding rows are the zero rows ``gather_rows`` gives sentinel tokens;
whatever they compute (a bias, at most) is annihilated by their zero
combine gate, which is applied in-kernel before the write.

Backward is flash-style recompute, wired in ``kernels.ops``: only xs-level
residuals are saved and the hidden is rebuilt tile-wise from the existing
ESMM/ESFK ops.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import ACTIVATIONS, pallas_interpret_default
from repro.kernels import tiles
from repro.quant.core import dequant_tile, tile_scales

_CONTRACT_K = (((1,), (0,)), ((), ()))  # row-major matmul: (m,k)x(k,n)


def esffn_cost(
    np_rows: int,
    d: int,
    f: int,
    num_blocks: int,
    itemsize: int,
    *,
    glu: bool,
    has_b1: bool = False,
    has_b2: bool = False,
    weight_bits: int | None = None,
) -> pl.CostEstimate:
    """Cost model of the fused FFN pass.

    ``bytes_accessed`` counts the gathered token rows, one expert weight
    tile per M-block, the gate vector and the sorted output — and, by
    construction, EXCLUDES the (Np, F) hidden round-trip the unfused
    composition pays (2 HBM writes + 2..3 reads of g/u/h between stages).
    The row gather ahead of the call (``gather_rows``) is an XLA op of its
    own: its (Np, D) write and the kernel's read of it are the sorted-copy
    round-trip the autotune model charges the fused path too.

    ``weight_bits`` (DESIGN.md §8) overrides the weight itemsize for
    quantized experts: int8/fp8 payloads move 8 bits per element across
    HBM regardless of the activation dtype, which is what shifts the
    autotune data-/model-centric crossover (block-wise scales add
    ~``(128*128)``-fold fewer bytes and are excluded).
    """
    n_mm = 3 if glu else 2
    flops = n_mm * 2 * np_rows * d * f
    w_itemsize = itemsize if weight_bits is None else weight_bits // 8
    w_bytes = num_blocks * n_mm * d * f * w_itemsize
    b_bytes = num_blocks * ((f if has_b1 else 0) + (d if has_b2 else 0)) * itemsize
    bytes_accessed = (
        np_rows * d * itemsize      # token rows gathered in
        + w_bytes + b_bytes         # one expert tile per m-block
        + np_rows * 4               # row_gate
        + np_rows * d * itemsize    # gate-weighted sorted output
    )
    return pl.CostEstimate(
        flops=flops, bytes_accessed=int(bytes_accessed),
        transcendentals=np_rows * f,
    )


def esffn_blocks(
    bm: int,
    d: int,
    f: int,
    *,
    glu: bool,
    x_itemsize: int,
    w_itemsize: int,
    quant_tiles: tuple[int, int] | None = None,
    has_b1: bool = False,
    has_b2: bool = False,
) -> int:
    """``bf``, the hidden block of an esffn call with row block ``bm`` at
    width ``d`` and hidden ``f``: all of ``f`` where the expert's whole
    weights fit the VMEM budget (module ``tiles``), so they stay resident over
    its row blocks. ``quant_tiles``: a quantized weight's tile along d and
    f; the one along f must divide ``bf``."""
    (bf,) = tiles.choose(
        (f,),
        lambda b: esffn_vmem(bm, d, *b, glu=glu, x_itemsize=x_itemsize,
                             w_itemsize=w_itemsize, quant_tiles=quant_tiles,
                             has_b1=has_b1, has_b2=has_b2),
        tiles=None if quant_tiles is None else quant_tiles[1:])
    return bf


def esffn_vmem(bm, d, bf, *, glu, x_itemsize, w_itemsize, quant_tiles=None,
               has_b1=False, has_b2=False) -> int:
    """VMEM footprint of one esffn call at blocks ``(bm, bf)``."""
    f32 = 4
    n_w = 3 if glu else 2
    blocks = [((bm, d), x_itemsize), ((bm, 1), f32), ((bm, d), x_itemsize)]
    blocks += [((d, bf), w_itemsize)] * (n_w - 1) + [((bf, d), w_itemsize)]
    if has_b1:
        blocks.append(((1, bf), x_itemsize))
    if has_b2:
        blocks.append(((1, d), x_itemsize))
    # The accumulator, the down dot's result and the hidden tiles (g, u,
    # h); a quantized tile is widened to f32 beside its broadcast scales.
    scratch = [((bm, d), f32)] * 2 + [((bm, bf), f32)] * n_w
    if quant_tiles is not None:
        td, tf = quant_tiles
        blocks += [((d // td, bf // tf), f32)] * (n_w - 1)
        blocks.append(((bf // tf, d // td), f32))
        scratch += [((d, bf), f32)] * (2 * n_w)
    return tiles.vmem_bytes(blocks, scratch)


def _wtile(w_ref, s_ref):
    """One expert weight tile, dequantized in VMEM when quantized
    (DESIGN.md §8) — only the int8/fp8 bytes crossed HBM."""
    if s_ref is None:
        return w_ref[0]
    return dequant_tile(w_ref[0], s_ref[0, 0, 0])


def _esffn_glu_kernel(
    block_expert,  # scalar prefetch (num_blocks,)
    x_ref,         # (BLK_M, D) gathered token rows
    *rest,         # wg [sg] wu [su] wd [sd] gate o acc
    act_fn,
    quantized: bool,
):
    rest = list(rest)
    wg_ref = rest.pop(0)
    sg_ref = rest.pop(0) if quantized else None
    wu_ref = rest.pop(0)
    su_ref = rest.pop(0) if quantized else None
    wd_ref = rest.pop(0)
    sd_ref = rest.pop(0) if quantized else None
    gate_ref, o_ref, acc = rest

    fb = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(fb == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    # One read of the x tile feeds BOTH projections (the GLU sharing).
    g = jax.lax.dot_general(
        x, _wtile(wg_ref, sg_ref), _CONTRACT_K,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    u = jax.lax.dot_general(
        x, _wtile(wu_ref, su_ref), _CONTRACT_K,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    # (BLK_M, BLK_F), VMEM only — never written to HBM. The activation
    # runs in float32: Mosaic has no bf16 lowering of its constants.
    h = (act_fn(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    acc[...] += jax.lax.dot_general(
        h, _wtile(wd_ref, sd_ref), _CONTRACT_K,
        preferred_element_type=jnp.float32,
    )

    @pl.when(fb == nf - 1)
    def _flush():
        o_ref[...] = (
            acc[...] * gate_ref[...].astype(jnp.float32)
        ).astype(o_ref.dtype)


def _esffn_mlp_kernel(
    block_expert,
    x_ref,         # (BLK_M, D) gathered token rows
    w1_ref,        # (1, D, BLK_F)
    *rest,         # [s1], [b1 (1, 1, BLK_F)], w2 (1, BLK_F, D), [s2],
                   # [b2 (1, 1, D)], gate, o, acc
    act_fn,
    has_b1: bool,
    has_b2: bool,
    quantized: bool,
):
    rest = list(rest)
    s1_ref = rest.pop(0) if quantized else None
    b1_ref = rest.pop(0) if has_b1 else None
    w2_ref = rest.pop(0)
    s2_ref = rest.pop(0) if quantized else None
    b2_ref = rest.pop(0) if has_b2 else None
    gate_ref, o_ref, acc = rest

    fb = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(fb == 0)
    def _init():
        if has_b2:
            # b2 is added once per row, not once per hidden tile.
            acc[...] = jnp.broadcast_to(
                b2_ref[0].astype(jnp.float32), acc.shape
            )
        else:
            acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    z = jax.lax.dot_general(
        x, _wtile(w1_ref, s1_ref), _CONTRACT_K,
        preferred_element_type=jnp.float32,
    )
    if has_b1:
        z = z + b1_ref[0].astype(jnp.float32)
    h = act_fn(z.astype(x.dtype).astype(jnp.float32)).astype(x.dtype)
    acc[...] += jax.lax.dot_general(
        h, _wtile(w2_ref, s2_ref), _CONTRACT_K,
        preferred_element_type=jnp.float32,
    )

    @pl.when(fb == nf - 1)
    def _flush():
        o_ref[...] = (
            acc[...] * gate_ref[...].astype(jnp.float32)
        ).astype(o_ref.dtype)


def _call(kernel, xs, row_gate, block_expert, tensor_args,
          tensor_specs, f_dim, bf, vmem, cost, interpret):
    np_rows, d = xs.shape
    nm = block_expert.shape[0]
    bm = np_rows // nm
    assert f_dim % bf == 0, (f_dim, bf)
    grid = (nm, f_dim // bf)

    in_specs = [pl.BlockSpec((bm, d), lambda m, fb, be: (m, 0))] + (
        tensor_specs + [pl.BlockSpec((bm, 1), lambda m, fb, be: (m, 0))]
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, d), lambda m, fb, be: (m, 0)),
            scratch_shapes=[pltpu.VMEM((bm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((np_rows, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=tiles.vmem_limit(vmem),
        ),
        cost_estimate=cost,
        interpret=interpret,
    )(block_expert, xs, *tensor_args,
      row_gate.reshape(np_rows, 1).astype(jnp.float32))


def _resolve_bf(bf, xs, block_expert, w_up, w_scales, *, glu,
                has_b1=False, has_b2=False):
    """The hidden block (``esffn_blocks`` unless given) and the call's
    VMEM footprint at it."""
    np_rows, d = xs.shape
    f = w_up.shape[2]
    nm = block_expert.shape[0]
    assert np_rows % nm == 0, (np_rows, nm)
    quant_tiles = None
    if w_scales is not None:
        s_up, s_down = w_scales[0], w_scales[-1]
        quant_tiles = (math.lcm(d // s_up.shape[1], d // s_down.shape[2]),
                       math.lcm(f // s_up.shape[2], f // s_down.shape[1]))
    kw = dict(glu=glu, x_itemsize=xs.dtype.itemsize,
              w_itemsize=w_up.dtype.itemsize, quant_tiles=quant_tiles,
              has_b1=has_b1, has_b2=has_b2)
    bm = np_rows // nm
    bf = min(bf or esffn_blocks(bm, d, f, **kw), f)
    return bf, esffn_vmem(bm, d, bf, **kw)


def _scale_arg(sc, wdims, bdims, index_map):
    """A weight's scale operand, tiled congruently with its weight
    BlockSpec (``quant.core.tile_scales``), and that operand's BlockSpec."""
    st = tile_scales(sc, wdims, bdims)
    return st, pl.BlockSpec((1, 1, 1) + st.shape[3:],
                            lambda *a: index_map(*a) + (0, 0))


@functools.partial(jax.jit, static_argnames=("act", "bf", "interpret"))
def esffn_glu_pallas(
    xs: jax.Array,
    row_gate: jax.Array,
    block_expert: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    w_scales=None,
    act: str = "silu",
    bf: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused GLU expert FFN: (Np, D) sorted rows -> (Np, D) gate-weighted
    sorted output, in one Pallas pass.

    xs: (Np, D) from ``core.reindex.gather_rows``; row_gate: (Np,) and
    block_expert: (Np // blk,) from ``core.reindex``; w_gate/w_up:
    (E, D, F); w_down: (E, F, D). ``w_scales``
    (DESIGN.md §8): (sg, su, sd) block-wise scales of int8/fp8 weights —
    each weight tile is dequantized in VMEM right before its MXU
    contraction, so the quantized bytes are what cross HBM.
    """
    if interpret is None:
        interpret = pallas_interpret_default()
    d = xs.shape[1]
    e, dw, f = w_gate.shape
    assert dw == d and w_up.shape == (e, d, f) and w_down.shape == (e, f, d)
    nm = block_expert.shape[0]
    bf_r, vmem = _resolve_bf(bf, xs, block_expert, w_gate, w_scales,
                             glu=True)
    quantized = w_scales is not None
    kernel = functools.partial(
        _esffn_glu_kernel, act_fn=ACTIVATIONS[act], quantized=quantized
    )
    up_map = lambda m, fb, be: (be[m], 0, fb)    # noqa: E731
    down_map = lambda m, fb, be: (be[m], fb, 0)  # noqa: E731
    args, specs = [], []
    for wt, sc, wdims, bdims, imap in (
        (w_gate, None if not quantized else w_scales[0], (d, f),
         (d, bf_r), up_map),
        (w_up, None if not quantized else w_scales[1], (d, f),
         (d, bf_r), up_map),
        (w_down, None if not quantized else w_scales[2], (f, d),
         (bf_r, d), down_map),
    ):
        args.append(wt)
        specs.append(pl.BlockSpec((1,) + bdims, imap))
        if quantized:
            st, spec = _scale_arg(sc, wdims, bdims, imap)
            args.append(st)
            specs.append(spec)
    cost = esffn_cost(
        xs.shape[0], d, f, nm, xs.dtype.itemsize, glu=True,
        weight_bits=8 * w_gate.dtype.itemsize,
    )
    return _call(kernel, xs, row_gate, block_expert,
                 args, specs, f, bf_r, vmem, cost, interpret)


@functools.partial(jax.jit, static_argnames=("act", "bf", "interpret"))
def esffn_mlp_pallas(
    xs: jax.Array,
    row_gate: jax.Array,
    block_expert: jax.Array,
    w1: jax.Array,
    b1: jax.Array | None,
    w2: jax.Array,
    b2: jax.Array | None,
    *,
    w_scales=None,
    act: str = "gelu",
    bf: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused 2-MLP expert FFN (optionally biased); see ``esffn_glu_pallas``.

    w1: (E, D, F); b1: (E, F) or None; w2: (E, F, D); b2: (E, D) or None.
    ``w_scales``: (s1, s2) block-wise scales of quantized w1/w2 (biases
    stay full precision).
    """
    if interpret is None:
        interpret = pallas_interpret_default()
    d = xs.shape[1]
    e, dw, f = w1.shape
    assert dw == d and w2.shape == (e, f, d)
    nm = block_expert.shape[0]
    bf_r, vmem = _resolve_bf(bf, xs, block_expert, w1, w_scales, glu=False,
                             has_b1=b1 is not None, has_b2=b2 is not None)
    quantized = w_scales is not None
    kernel = functools.partial(
        _esffn_mlp_kernel, act_fn=ACTIVATIONS[act],
        has_b1=b1 is not None, has_b2=b2 is not None, quantized=quantized,
    )
    up_map = lambda m, fb, be: (be[m], 0, fb)    # noqa: E731
    down_map = lambda m, fb, be: (be[m], fb, 0)  # noqa: E731
    args = [w1]
    specs = [pl.BlockSpec((1, d, bf_r), up_map)]
    if quantized:
        st, spec = _scale_arg(w_scales[0], (d, f), (d, bf_r), up_map)
        args.append(st)
        specs.append(spec)
    # Biases ride as (E, 1, width): a (1, 1, block) tile satisfies the
    # (8, 128) block rule, a (1, block) tile of (E, width) does not.
    if b1 is not None:
        assert b1.shape == (e, f)
        args.append(b1.reshape(e, 1, f))
        specs.append(pl.BlockSpec((1, 1, bf_r), up_map))
    args.append(w2)
    specs.append(pl.BlockSpec((1, bf_r, d), down_map))
    if quantized:
        st, spec = _scale_arg(w_scales[1], (f, d), (bf_r, d), down_map)
        args.append(st)
        specs.append(spec)
    if b2 is not None:
        assert b2.shape == (e, d)
        args.append(b2.reshape(e, 1, d))
        specs.append(pl.BlockSpec((1, 1, d), lambda m, fb, be: (be[m], 0, 0)))
    cost = esffn_cost(
        xs.shape[0], d, f, nm, xs.dtype.itemsize, glu=False,
        has_b1=b1 is not None, has_b2=b2 is not None,
        weight_bits=8 * w1.dtype.itemsize,
    )
    return _call(kernel, xs, row_gate, block_expert,
                 args, specs, f, bf_r, vmem, cost, interpret)
