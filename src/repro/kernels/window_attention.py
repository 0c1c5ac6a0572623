"""Fused window attention (Pallas TPU), forward and backward. DESIGN.md §5.

The Swin block's attention core, from the qkv projection's output to the
proj input:

  per window and head h:  s = q kᵀ · hd^-0.5 + bias[h];  p = softmax(s);
                          o = p v

The caller keeps in XLA the shift rolls, the window partition and reverse,
and the two projections. It hands over ``qkv`` as the projection writes
it, ``(nW·N, 3C)``: N = window² rows per window, windows one after
another, columns in (q|k|v, head, hd) order, C = heads · hd. The output
is ``(nW·N, C)``, so no relayout sits between the kernel and either
projection.

Windows share a score tile: ``windows_per_tile`` packs as many whole
windows as fit the 128 lanes (two of 49 rows), and the bias is laid out
per tile with ``MASK`` between different windows, so each key outside a
query's window gets no weight. The kernels see tiles of n rows. A grid
step takes G tiles, a ``(G·n, 3C)`` block; a loop inside it takes the
fewest tiles whose rows are a multiple of 8, regroups their rows in VMEM
as ``(tiles, n, 3C)``, and takes each head's q, k and v as the lane
ranges ``[h·hd, (h+1)·hd)`` of the q, k and v thirds. The n × n scores
and probabilities exist only in VMEM.

Scores are held transposed, ``sT[key, query]``: the softmax reduces over
sublanes, and the per-query logsumexp comes out as a lane row, saved as
``(tiles, heads, n)``.

The backward recomputes p from q, k and the logsumexp, and writes dq, dk
and dv into one ``(G·n, 3C)`` block of dqkv; ``rowsum(do ⊙ o)`` is taken
as ``rowsum(p ⊙ dp)``, so the output is not saved. The bias gradient
Σ ds over tiles accumulates across the grid (an "arbitrary" axis) in the
``(heads, n, n)`` output; XLA adds up its diagonal blocks.

Precision is the configuration's: every dot runs at the platform's
default precision, as XLA's dots and the expert kernels' do (on the TPU
one bfloat16 pass over float32 operands, float32 accumulation; on the CPU
float32), and the softmax, the logsumexp and ds are float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import pallas_interpret_default
from repro.kernels import tiles

F32 = jnp.float32
#: The bias between windows that share a score tile: exp() of it is 0.
MASK = -1e30

# Tile-batched dot_general dimension numbers; batch is axis 0.
_NT = (((2,), (2,)), ((0,), (0,)))  # (g, a, d) x (g, b, d) -> (g, a, b)
_NN = (((2,), (1,)), ((0,), (0,)))  # (g, a, k) x (g, k, b) -> (g, a, b)
_TN = (((1,), (1,)), ((0,), (0,)))  # (g, k, a) x (g, k, b) -> (g, a, b)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


def _rows(i, sub, n):
    """The block's rows of tiles ``[i·sub, (i+1)·sub)``."""
    return pl.ds(pl.multiple_of(i * sub * n, sub * n), sub * n)


def _fwd_kernel(qkv_ref, bias_ref, o_ref, lse_ref, x3, o3, *, heads, hd,
                sub):
    c = heads * hd
    scale = hd ** -0.5

    def body(i, carry):
        rows = _rows(i, *x3.shape[:2])
        x3[...] = qkv_ref[rows, :].reshape(x3.shape)
        for h in range(heads):
            q = x3[:, :, h * hd:(h + 1) * hd]
            k = x3[:, :, c + h * hd:c + (h + 1) * hd]
            v = x3[:, :, 2 * c + h * hd:2 * c + (h + 1) * hd]
            st = _dot(k, q, _NT) * scale + bias_ref[h][None]
            m = st.max(axis=1, keepdims=True)
            pt = jnp.exp(st - m)
            l = pt.sum(axis=1, keepdims=True)
            o3[:, :, h * hd:(h + 1) * hd] = _dot(pt * (1.0 / l), v, _TN)
            lse_ref[pl.ds(i * sub, sub), h:h + 1, :] = m + jnp.log(l)
        o_ref[rows, :] = o3[...].reshape(-1, c).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, lse_ref.shape[0] // sub, body, 0)


def _bwd_kernel(qkv_ref, do_ref, lse_ref, bias_ref, dqkv_ref, db_ref, x3,
                do3, dx3, *, heads, hd, sub):
    c = heads * hd
    scale = hd ** -0.5

    @pl.when(pl.program_id(0) == 0)
    def _init():
        db_ref[...] = jnp.zeros_like(db_ref)

    def body(i, carry):
        rows = _rows(i, *x3.shape[:2])
        x3[...] = qkv_ref[rows, :].reshape(x3.shape)
        do3[...] = do_ref[rows, :].reshape(do3.shape)
        for h in range(heads):
            qs = slice(h * hd, (h + 1) * hd)
            ks = slice(c + h * hd, c + (h + 1) * hd)
            vs = slice(2 * c + h * hd, 2 * c + (h + 1) * hd)
            q, k, v = x3[:, :, qs], x3[:, :, ks], x3[:, :, vs]
            do = do3[:, :, qs]
            st = _dot(k, q, _NT) * scale + bias_ref[h][None]
            pt = jnp.exp(st - lse_ref[pl.ds(i * sub, sub), h:h + 1, :])
            dpt = _dot(v, do, _NT)
            dst = pt * (dpt - (pt * dpt).sum(axis=1, keepdims=True))
            dx3[:, :, qs] = _dot(dst, k, _TN) * scale
            dx3[:, :, ks] = _dot(dst, q, _NN) * scale
            dx3[:, :, vs] = _dot(pt, do, _NN)
            db_ref[h] += dst.sum(axis=0)
        dqkv_ref[rows, :] = dx3[...].reshape(-1, 3 * c).astype(dqkv_ref.dtype)
        return carry

    jax.lax.fori_loop(0, lse_ref.shape[0] // sub, body, 0)


def _step(n: int) -> int:
    """The fewest tiles of n rows whose rows are a multiple of the 8
    sublanes."""
    return 8 // math.gcd(n, 8)


def _sub(g: int, n: int) -> int:
    """Tiles per loop step inside a grid step: ``_step(n)``, so each loop
    step's rows start on a sublane tile, or all G where that does not
    divide them (G is then the whole tile count)."""
    return _step(n) if g % _step(n) == 0 else g


def window_attention_vmem(g: int, n: int, c: int, heads: int,
                          itemsize: int, *, backward: bool) -> int:
    """VMEM footprint of one call at G = ``g`` tiles of n rows per grid
    step."""
    f32 = 4
    hd = c // heads
    blocks = [((g * n, 3 * c), itemsize), ((heads, n, n), f32),
              ((g, heads, n), f32)]
    if backward:
        # do in; dqkv and the bias gradient out.
        blocks += [((g * n, c), itemsize), ((g * n, 3 * c), itemsize),
                   ((heads, n, n), f32)]
    else:
        blocks += [((g * n, c), itemsize)]
    s = _sub(g, n)
    # One loop step's live values: its rows (and their gradient rows)
    # regrouped by tile, one head's score-sized temporaries and the
    # hd-wide slices.
    scratch = [((s, n, 3 * c), f32)] * (4 if backward else 2) \
        + [((s, n, n), f32)] * (8 if backward else 4) \
        + [((s, n, hd), f32)] * (8 if backward else 4)
    return tiles.vmem_bytes(blocks, scratch)


def window_attention_blocks(nw: int, n: int, c: int, heads: int,
                            itemsize: int) -> int:
    """G: the largest divisor of the tile count ``nw`` whose backward (the
    larger footprint) fits the VMEM budget (module ``tiles``), a whole
    number of loop steps (``_sub``) unless it is the whole count; where
    none fits, the smallest."""
    options = [g for g in range(nw, 0, -1)
               if nw % g == 0 and (g % _step(n) == 0 or g == nw)]
    fits = [g for g in options if window_attention_vmem(
        g, n, c, heads, itemsize, backward=True) <= tiles.VMEM_BUDGET]
    return fits[0] if fits else options[-1]


def _cost(nw, n, c, heads, itemsize, *, backward):
    hd = c // heads
    dots = 5 if backward else 2
    act = nw * n * c * itemsize
    return pl.CostEstimate(
        flops=2 * dots * nw * heads * n * n * hd,
        transcendentals=nw * heads * n * (n + (0 if backward else 1)),
        bytes_accessed=(7 if backward else 4) * act + nw * heads * n * 4)


@functools.partial(jax.jit, static_argnames=("heads", "n", "g", "interpret"))
def window_attn_fwd_pallas(qkv, bias_t, *, heads: int, n: int, g: int,
                           interpret: bool = False):
    """qkv (tiles·n, 3C), bias_t (heads, n_key, n_query) ->
    out (tiles·n, C), logsumexp (tiles, heads, n) float32."""
    rows, c3 = qkv.shape
    nw, c = rows // n, c3 // 3
    vmem = window_attention_vmem(g, n, c, heads, qkv.dtype.itemsize,
                                 backward=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, hd=c // heads,
                          sub=_sub(g, n)),
        grid=(nw // g,),
        in_specs=[pl.BlockSpec((g * n, c3), lambda i: (i, 0)),
                  pl.BlockSpec((heads, n, n), lambda i: (0, 0, 0))],
        out_specs=[pl.BlockSpec((g * n, c), lambda i: (i, 0)),
                   pl.BlockSpec((g, heads, n), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, c), qkv.dtype),
                   jax.ShapeDtypeStruct((nw, heads, n), F32)],
        scratch_shapes=[pltpu.VMEM((_sub(g, n), n, c3), qkv.dtype),
                        pltpu.VMEM((_sub(g, n), n, c), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=tiles.vmem_limit(vmem)),
        cost_estimate=_cost(nw, n, c, heads, qkv.dtype.itemsize,
                            backward=False),
        interpret=interpret,
    )(qkv, bias_t)


@functools.partial(jax.jit, static_argnames=("heads", "n", "g", "interpret"))
def window_attn_bwd_pallas(qkv, do, lse, bias_t, *, heads: int, n: int,
                           g: int, interpret: bool = False):
    """-> dqkv (tiles·n, 3C), dbias_t (heads, n_key, n_query) float32."""
    rows, c3 = qkv.shape
    nw, c = rows // n, c3 // 3
    vmem = window_attention_vmem(g, n, c, heads, qkv.dtype.itemsize,
                                 backward=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, hd=c // heads,
                          sub=_sub(g, n)),
        grid=(nw // g,),
        in_specs=[pl.BlockSpec((g * n, c3), lambda i: (i, 0)),
                  pl.BlockSpec((g * n, c), lambda i: (i, 0)),
                  pl.BlockSpec((g, heads, n), lambda i: (i, 0, 0)),
                  pl.BlockSpec((heads, n, n), lambda i: (0, 0, 0))],
        out_specs=[pl.BlockSpec((g * n, c3), lambda i: (i, 0)),
                   pl.BlockSpec((heads, n, n), lambda i: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, c3), qkv.dtype),
                   jax.ShapeDtypeStruct((heads, n, n), F32)],
        scratch_shapes=[pltpu.VMEM((_sub(g, n), n, c3), qkv.dtype),
                        pltpu.VMEM((_sub(g, n), n, c), do.dtype),
                        pltpu.VMEM((_sub(g, n), n, c3), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=tiles.vmem_limit(vmem)),
        cost_estimate=_cost(nw, n, c, heads, qkv.dtype.itemsize,
                            backward=True),
        interpret=interpret,
    )(qkv, do, lse, bias_t)


def windows_per_tile(nw: int, n: int) -> int:
    """Windows whose N-row blocks share one score tile: as many as fit
    the 128 lanes and divide the window count (2 for 7 × 7 windows)."""
    return max(p for p in range(1, max(1, tiles.LANE // n) + 1)
               if nw % p == 0)


def _tiled_bias(bias, p):
    """(N_query, N_key, heads) -> (heads, pN_key, pN_query): each window's
    bias on the diagonal, ``MASK`` across windows."""
    bias_t = bias.transpose(2, 1, 0).astype(F32)
    if p == 1:
        return bias_t
    heads, n, _ = bias_t.shape
    same = jnp.eye(p, dtype=bool)[None, :, None, :, None]
    return jnp.where(same, bias_t[:, None, :, None, :], MASK).reshape(
        heads, p * n, p * n)


def _untiled(dbias_t, p):
    """The bias gradient of a tile back to one window's, (N, N, heads)."""
    heads, t, _ = dbias_t.shape
    n = t // p
    blocks = dbias_t.reshape(heads, p, n, p, n)
    return jnp.diagonal(blocks, axis1=1, axis2=3).sum(-1).transpose(2, 1, 0)


def _call(qkv, bias, heads):
    """Windows per tile, and the kernels' keyword arguments, for windows
    of ``bias``'s N rows."""
    n = bias.shape[0]
    p = windows_per_tile(qkv.shape[0] // n, n)
    g = window_attention_blocks(qkv.shape[0] // (p * n), p * n,
                                qkv.shape[1] // 3, heads, qkv.dtype.itemsize)
    return p, dict(heads=heads, n=p * n, g=g,
                   interpret=pallas_interpret_default())


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def window_attention(qkv, bias, heads):
    """Softmax attention inside each window.

    qkv: (nW·N, 3C), the qkv projection of each window's N tokens, the
    windows one after another; bias: (N_query, N_key, heads), the
    gathered relative-position bias. Returns the attention output
    (nW·N, C), heads concatenated along C.
    """
    return _fwd(qkv, bias, heads)[0]


def _fwd(qkv, bias, heads):
    p, kw = _call(qkv, bias, heads)
    out, lse = window_attn_fwd_pallas(qkv, _tiled_bias(bias, p), **kw)
    return out, (qkv, bias, lse)


def _bwd(heads, res, do):
    qkv, bias, lse = res
    p, kw = _call(qkv, bias, heads)
    dqkv, dbias_t = window_attn_bwd_pallas(qkv, do, lse,
                                           _tiled_bias(bias, p), **kw)
    return dqkv, _untiled(dbias_t, p)


window_attention.defvjp(_fwd, _bwd)
