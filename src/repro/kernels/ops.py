"""Public expert-specific ops: impl dispatch + custom autodiff.

Three implementations of the same zero-redundancy semantics over the
expert-sorted layout (see ``core.reindex``), named by ``impl``:

  - ``pallas``  — the paper-faithful TPU kernels (esmm/esfk/ess/estmm and
    the fused esffn); the default on the TPU. Off the TPU the tests run
    them in interpret mode.
  - ``blocked`` — batched block-diagonal einsums in XLA; the default off
    the TPU.
  - ``ref``     — pure-jnp one-hot oracle that the tests compare against.

``get_default_impl`` chooses between ``pallas`` and ``blocked`` by
platform; every public op raises ``ValueError`` for any other name.

The backward pass is wired by ``custom_vjp`` exactly as the paper's Table 5:
dX via ESMM with transposed weights, (dW, db) via the fused ESFK (or the
unfused ESTMM + ESS pair when ``fused=False``, paper Fig. 12 ablation).

The forward-side fusion (DESIGN.md §5) lives here too: ``esffn_glu`` /
``esffn_mlp`` run the whole expert FFN — gather, up/gate, activation, down,
gate weighting — as ONE op (the Pallas megakernel ``kernels.esffn`` on TPU,
a single fused XLA region for ``blocked``), with a flash-style custom_vjp
that saves only xs-level residuals and recomputes the hidden tile-wise in
the backward from the ESMM/ESFK ops above.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common import ACTIVATIONS, on_tpu
from repro.kernels import ref as _ref
from repro.quant.core import dequantize_blockwise
from repro.kernels.esmm import esmm_pallas
from repro.kernels.esffn import esffn_glu_pallas, esffn_mlp_pallas
from repro.kernels.esfk import esfk_pallas
from repro.kernels.ess import ess_pallas
from repro.kernels.estmm import estmm_pallas

_FUSED_BACKWARD: bool = True


def get_default_impl() -> str:
    return "pallas" if on_tpu() else "blocked"


def default_fused_ffn(impl: Optional[str] = None) -> bool:
    """Whether the fused forward FFN (DESIGN.md §5) is on by default.

    On for the TPU ``pallas`` path, where the megakernel removes real HBM
    round-trips; the XLA impls keep the unfused composition unless the
    caller (``ParallelConfig.fused_ffn`` / espec's ``fused=``) opts in.
    """
    return (impl or get_default_impl()) == "pallas"


# ---------------------------------------------------------------------------
# blocked (batched block-diagonal einsum) implementation
#
# Exploits the sorted layout's invariant directly in XLA: every BLK-row
# block uses ONE expert, so the grouped matmul is a plain batched matmul
# against per-block gathered weight tiles. Compiled FLOPs equal the
# zero-redundancy count (Np * D1 * D2 * 2) exactly — unlike XLA's grouped
# dot, whose CPU lowering computes every group densely (E x redundancy).
# This is the CPU execution path; on TPU the Pallas kernels replace it
# (the per-block weight gather becomes the scalar-prefetched DMA).
# ---------------------------------------------------------------------------

def _blocked_esmm(xs, w, b, block_expert, transpose_rhs, w_scales=None):
    np_rows = xs.shape[0]
    nblk = block_expert.shape[0]
    blk = np_rows // nblk
    xb = xs.reshape(nblk, blk, -1)
    wb = w[block_expert]  # (nblk, D1, D2) or (nblk, D2, D1)
    if w_scales is not None:
        # int8/fp8 tiles gathered per block (the quantized bytes are what
        # move), dequantized block-wise right before the contraction —
        # the XLA analogue of the kernel's VMEM dequant (DESIGN.md §8).
        wb = dequantize_blockwise(wb, w_scales[block_expert], dtype=xs.dtype)
    if transpose_rhs:
        y = jnp.einsum(
            "gbk,gnk->gbn", xb, wb, preferred_element_type=xs.dtype
        )
    else:
        y = jnp.einsum(
            "gbk,gkn->gbn", xb, wb, preferred_element_type=xs.dtype
        )
    if b is not None:
        y = y + b[block_expert][:, None].astype(y.dtype)
    return y.reshape(np_rows, -1)


def _blocked_estmm(x1, x2, block_expert, num_experts):
    np_rows = x1.shape[0]
    nblk = block_expert.shape[0]
    blk = np_rows // nblk
    per_block = jnp.einsum(
        "gbd,gbf->gdf",
        x1.reshape(nblk, blk, -1),
        x2.reshape(nblk, blk, -1),
        preferred_element_type=jnp.float32,
    )
    out = jnp.zeros((num_experts,) + per_block.shape[1:], jnp.float32)
    return out.at[block_expert].add(per_block)


def _blocked_ess(x, block_expert, num_experts):
    blk = x.shape[0] // block_expert.shape[0]
    row_expert = jnp.repeat(block_expert, blk)
    return jax.ops.segment_sum(
        x.astype(jnp.float32),
        row_expert,
        num_segments=num_experts,
        indices_are_sorted=True,
    )


def set_fused_backward(fused: bool) -> None:
    """Toggle the ESFK fusion (paper Fig. 12 'fused kernel' ablation)."""
    global _FUSED_BACKWARD
    _FUSED_BACKWARD = fused


# ---------------------------------------------------------------------------
# impl dispatch (no autodiff)
# ---------------------------------------------------------------------------

def _esmm_any(impl, transpose_rhs, xs, w, b, block_expert, padded_counts,
              w_scales=None):
    if impl == "pallas":
        blk = xs.shape[0] // block_expert.shape[0]
        return esmm_pallas(
            xs, w, b, block_expert, w_scales=w_scales,
            transpose_rhs=transpose_rhs, bm=blk,
        )
    if impl == "blocked":
        return _blocked_esmm(xs, w, b, block_expert, transpose_rhs,
                             w_scales=w_scales)
    if impl == "ref":
        if w_scales is not None:
            # The oracle dequantizes up front.
            w = dequantize_blockwise(w, w_scales, dtype=xs.dtype)
        return _ref.esmm(xs, w, b, block_expert, transpose_rhs=transpose_rhs)
    raise ValueError(f"unknown impl {impl!r}")


def _esfk_any(impl, fused, x1, x2, block_expert, padded_counts, need_db):
    """(dW, db) with db=None when need_db is False."""
    e = padded_counts.shape[0]
    if impl == "pallas":
        blk = x1.shape[0] // block_expert.shape[0]
        if fused and need_db:
            dw, db = esfk_pallas(x1, x2, block_expert, padded_counts, bm=blk)
            return dw, db
        dw = estmm_pallas(x1, x2, block_expert, padded_counts, bm=blk)
        db = (
            ess_pallas(x2, block_expert, padded_counts, bm=blk)
            if need_db
            else None
        )
        return dw, db
    if impl == "blocked":
        dw = _blocked_estmm(x1, x2, block_expert, e)
        db = _blocked_ess(x2, block_expert, e) if need_db else None
        return dw, db
    if impl == "ref":
        dw = _ref.estmm(x1, x2, block_expert, e)
        db = _ref.ess(x2, block_expert, e) if need_db else None
        return dw, db
    raise ValueError(f"unknown impl {impl!r}")


# ---------------------------------------------------------------------------
# differentiable esmm (paper Table 5 wiring)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _esmm(impl, transpose_rhs, fused, xs, w, b, block_expert, padded_counts):
    return _esmm_any(impl, transpose_rhs, xs, w, b, block_expert, padded_counts)


def _esmm_fwd(impl, transpose_rhs, fused, xs, w, b, block_expert, padded_counts):
    y = _esmm_any(impl, transpose_rhs, xs, w, b, block_expert, padded_counts)
    return y, (xs, w, b is not None, block_expert, padded_counts)


def _esmm_bwd(impl, transpose_rhs, fused, res, dy):
    xs, w, has_b, block_expert, padded_counts = res
    # dX: ESMM with the opposite weight orientation (paper rows 6/10).
    dxs = _esmm_any(
        impl, not transpose_rhs, dy, w, None, block_expert, padded_counts
    )
    # dW (ESTMM) + db (ESS), fused as ESFK (paper rows 4/5/8/9).
    if transpose_rhs:
        dw, db = _esfk_any(
            impl, fused, dy, xs, block_expert, padded_counts, has_b
        )
    else:
        dw, db = _esfk_any(
            impl, fused, xs, dy, block_expert, padded_counts, has_b
        )
    dw = dw.astype(w.dtype)
    if db is not None:
        db = db.astype(dy.dtype)
    return (dxs, dw, db if has_b else None, None, None)


_esmm.defvjp(_esmm_fwd, _esmm_bwd)


# Quantized-weight ESMM (DESIGN.md §8): the int8/fp8 payload + block scales
# go through the fused-dequant kernels in forward; backward flows dX (and
# db) against the dequantized weights. The payload itself is frozen — no
# dW: training-side quantization is the STE ``quant.core.fake_quant`` on
# the full-precision master weights, not gradients into int8.

def _zero_cot(x):
    """Cotangent for a frozen operand: zeros for inexact dtypes (fp8
    scales/payloads), None for integer payloads (jax float0)."""
    return jnp.zeros_like(x) if jnp.issubdtype(x.dtype, jnp.inexact) else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _esmm_q(impl, transpose_rhs, xs, w, w_scales, b, block_expert,
            padded_counts):
    return _esmm_any(impl, transpose_rhs, xs, w, b, block_expert,
                     padded_counts, w_scales=w_scales)


def _esmm_q_fwd(impl, transpose_rhs, xs, w, w_scales, b, block_expert,
                padded_counts):
    y = _esmm_any(impl, transpose_rhs, xs, w, b, block_expert,
                  padded_counts, w_scales=w_scales)
    return y, (xs, w, w_scales, b is not None, block_expert, padded_counts)


def _esmm_q_bwd(impl, transpose_rhs, res, dy):
    xs, w, w_scales, has_b, block_expert, padded_counts = res
    w_dq = dequantize_blockwise(w, w_scales, dtype=xs.dtype)
    dxs = _esmm_any(
        impl, not transpose_rhs, dy, w_dq, None, block_expert, padded_counts
    )
    db = None
    if has_b:
        db = ess(dy, block_expert, padded_counts, impl=impl).astype(dy.dtype)
    return (dxs, _zero_cot(w), jnp.zeros_like(w_scales),
            db if has_b else None, None, None)


_esmm_q.defvjp(_esmm_q_fwd, _esmm_q_bwd)


def esmm(
    xs: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array],
    block_expert: jax.Array,
    padded_counts: jax.Array,
    *,
    w_scales: Optional[jax.Array] = None,
    transpose_rhs: bool = False,
    impl: Optional[str] = None,
    fused: Optional[bool] = None,
) -> jax.Array:
    """Differentiable expert-specific matmul on the sorted layout.

    xs: (Np, K); w: (E, K, N) — or (E, N, K) with transpose_rhs; b: (E, N)
    or None; block_expert/padded_counts from ``core.reindex.build_reindex``.
    ``w_scales``: block-wise scales of a quantized ``w`` (DESIGN.md §8) —
    dequant fuses into the kernels; the payload is frozen (dX/db only).
    """
    impl = impl or get_default_impl()
    fused = _FUSED_BACKWARD if fused is None else fused
    if w_scales is not None:
        return _esmm_q(impl, transpose_rhs, xs, w, w_scales, b,
                       block_expert, padded_counts)
    return _esmm(impl, transpose_rhs, fused, xs, w, b, block_expert, padded_counts)


# Non-differentiable public wrappers (the quantized backward, tests).

def ess(x, block_expert, padded_counts, *, impl=None):
    impl = impl or get_default_impl()
    e = padded_counts.shape[0]
    if impl == "pallas":
        blk = x.shape[0] // block_expert.shape[0]
        return ess_pallas(x, block_expert, padded_counts, bm=blk)
    if impl == "blocked":
        return _blocked_ess(x, block_expert, e)
    if impl == "ref":
        return _ref.ess(x, block_expert, e)
    raise ValueError(f"unknown impl {impl!r}")


def estmm(x1, x2, block_expert, padded_counts, *, impl=None):
    impl = impl or get_default_impl()
    e = padded_counts.shape[0]
    if impl == "pallas":
        blk = x1.shape[0] // block_expert.shape[0]
        return estmm_pallas(x1, x2, block_expert, padded_counts, bm=blk)
    if impl == "blocked":
        return _blocked_estmm(x1, x2, block_expert, e)
    if impl == "ref":
        return _ref.estmm(x1, x2, block_expert, e)
    raise ValueError(f"unknown impl {impl!r}")


def esfk(x1, x2, block_expert, padded_counts, *, impl=None, fused=True):
    impl = impl or get_default_impl()
    return _esfk_any(impl, fused, x1, x2, block_expert, padded_counts, True)


# ---------------------------------------------------------------------------
# fused expert FFN (DESIGN.md §5): gather -> up/gate -> act -> down -> gate
#
# One differentiable op per expert-body type. Forward impls:
#   pallas  — kernels.esffn megakernel (the (Np, F) hidden never hits HBM).
#   blocked — one fused XLA region: rows gathered straight from the unsorted
#             x, expert weight tiles formed by exact one-hot contraction.
#   ref     — staged composition of the oracle's esmms inside the op.
# Backward (all impls) is flash-style: residuals are xs-level only (x +
# row maps + weights); the hidden is recomputed and grads flow through the
# same ESMM/ESFK kernels as the unfused path (paper Table 5 wiring).
# ---------------------------------------------------------------------------

def _gather_rows(x, row_token):
    """(Np, D) sorted rows from unsorted x; sentinel rows (== N) are zero."""
    from repro.core.reindex import gather_rows

    return gather_rows(x, row_token)


def _blocked_wtiles(onehot, w):
    """Per-block expert tiles w[block_expert] as a one-hot contraction.

    Exact (each one-hot row has a single 1; adding exact zeros changes
    nothing), but XLA lowers it as a multithreaded matmul instead of the
    memory-bound gather — measurably faster on CPU, and only available to
    the fused op because it owns every stage of the pipeline.
    """
    return jnp.einsum("ge,e...->g...", onehot, w,
                      preferred_element_type=w.dtype)


def _blocked_esffn_glu(x, row_token, row_gate, block_expert, wg, wu, wd,
                       act_fn, scales=None):
    np_rows = row_token.shape[0]
    nblk = block_expert.shape[0]
    blk = np_rows // nblk
    xb = _gather_rows(x, row_token).reshape(nblk, blk, -1)
    if scales is not None:
        # int8/fp8 payloads: gather the quantized expert tiles per block
        # (the quantized bytes move) and dequantize block-wise just before
        # the contraction — the XLA analogue of the kernel's VMEM dequant.
        sg, su, sd = scales
        tiles = [
            dequantize_blockwise(w[block_expert], s[block_expert],
                                 dtype=x.dtype)
            for w, s in ((wg, sg), (wu, su), (wd, sd))
        ]
    else:
        onehot = jax.nn.one_hot(block_expert, wg.shape[0], dtype=wg.dtype)
        tiles = [_blocked_wtiles(onehot, w) for w in (wg, wu, wd)]
    g = jnp.einsum("gbd,gdf->gbf", xb, tiles[0],
                   preferred_element_type=x.dtype)
    u = jnp.einsum("gbd,gdf->gbf", xb, tiles[1],
                   preferred_element_type=x.dtype)
    h = act_fn(g) * u
    y = jnp.einsum("gbf,gfd->gbd", h, tiles[2],
                   preferred_element_type=x.dtype)
    y = y * row_gate.reshape(nblk, blk, 1).astype(y.dtype)
    return y.reshape(np_rows, -1)


def _blocked_esffn_mlp(x, row_token, row_gate, block_expert, w1, b1, w2, b2,
                       act_fn, scales=None):
    np_rows = row_token.shape[0]
    nblk = block_expert.shape[0]
    blk = np_rows // nblk
    xb = _gather_rows(x, row_token).reshape(nblk, blk, -1)
    onehot = jax.nn.one_hot(block_expert, w1.shape[0],
                            dtype=b1.dtype if b1 is not None else w1.dtype)
    if scales is not None:
        s1, s2 = scales
        t1 = dequantize_blockwise(w1[block_expert], s1[block_expert],
                                  dtype=x.dtype)
        t2 = dequantize_blockwise(w2[block_expert], s2[block_expert],
                                  dtype=x.dtype)
    else:
        t1 = _blocked_wtiles(onehot.astype(w1.dtype), w1)
        t2 = _blocked_wtiles(onehot.astype(w2.dtype), w2)
    z = jnp.einsum("gbd,gdf->gbf", xb, t1, preferred_element_type=x.dtype)
    if b1 is not None:
        z = z + _blocked_wtiles(onehot.astype(b1.dtype), b1)[:, None].astype(
            z.dtype)
    h = act_fn(z)
    y = jnp.einsum("gbf,gfd->gbd", h, t2, preferred_element_type=x.dtype)
    if b2 is not None:
        y = y + _blocked_wtiles(onehot.astype(b2.dtype), b2)[:, None].astype(
            y.dtype)
    y = y * row_gate.reshape(nblk, blk, 1).astype(y.dtype)
    return y.reshape(np_rows, -1)


def _staged_esffn(impl, act_fn, x, row_token, row_gate, block_expert,
                  padded_counts, glu, ws, scales=None):
    """Per-stage composition inside the fused op (the ``ref`` impl)."""
    xs = _gather_rows(x, row_token)
    if glu:
        wg, wu, wd = ws
        sg, su, sd = scales if scales is not None else (None, None, None)
        g = _esmm_any(impl, False, xs, wg, None, block_expert, padded_counts,
                      w_scales=sg)
        u = _esmm_any(impl, False, xs, wu, None, block_expert, padded_counts,
                      w_scales=su)
        h = act_fn(g) * u
        ys = _esmm_any(impl, False, h, wd, None, block_expert, padded_counts,
                       w_scales=sd)
    else:
        w1, b1, w2, b2 = ws
        s1, s2 = scales if scales is not None else (None, None)
        z = _esmm_any(impl, False, xs, w1, b1, block_expert, padded_counts,
                      w_scales=s1)
        h = act_fn(z)
        ys = _esmm_any(impl, False, h, w2, b2, block_expert, padded_counts,
                       w_scales=s2)
    return ys * row_gate[:, None].astype(ys.dtype)


def _esffn_fwd_any(impl, act, glu, x, row_token, row_gate, block_expert,
                   padded_counts, ws, scales=None):
    act_fn = ACTIVATIONS[act]
    if impl == "pallas":
        pallas_fn = esffn_glu_pallas if glu else esffn_mlp_pallas
        return pallas_fn(
            _gather_rows(x, row_token), row_gate, block_expert, *ws,
            w_scales=scales, act=act,
        )
    if impl == "blocked":
        if glu:
            return _blocked_esffn_glu(
                x, row_token, row_gate, block_expert, *ws, act_fn=act_fn,
                scales=scales,
            )
        return _blocked_esffn_mlp(
            x, row_token, row_gate, block_expert, *ws, act_fn=act_fn,
            scales=scales,
        )
    if impl == "ref":
        return _staged_esffn(
            impl, act_fn, x, row_token, row_gate, block_expert,
            padded_counts, glu, ws, scales=scales,
        )
    raise ValueError(f"unknown impl {impl!r}")


def _scatter_dx(x, row_token, dxs):
    """dX: scatter the sorted-row grads back to token order (pads dropped)."""
    return jnp.zeros_like(x).at[row_token].add(
        dxs.astype(x.dtype), mode="drop"
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _esffn_glu(impl, act, x, row_token, row_gate, block_expert,
               padded_counts, wg, wu, wd):
    return _esffn_fwd_any(
        impl, act, True, x, row_token, row_gate, block_expert,
        padded_counts, (wg, wu, wd),
    )


def _esffn_glu_fwd(impl, act, x, row_token, row_gate, block_expert,
                   padded_counts, wg, wu, wd):
    y = _esffn_fwd_any(
        impl, act, True, x, row_token, row_gate, block_expert,
        padded_counts, (wg, wu, wd),
    )
    # xs-level residuals only: no (Np, F) hidden is saved (flash-style).
    return y, (x, row_token, row_gate, block_expert, padded_counts,
               wg, wu, wd)


def _esffn_glu_bwd(impl, act, res, dys_w):
    """Flash-style recompute backward against DENSE weights; the quantized
    op's backward dequantizes first and reuses this body verbatim."""
    x, row_token, row_gate, block_expert, padded_counts, wg, wu, wd = res
    act_fn = ACTIVATIONS[act]
    fused = _FUSED_BACKWARD
    # Tile-wise recompute of the hidden from the xs-level residuals.
    xs = _gather_rows(x, row_token)
    g = _esmm_any(impl, False, xs, wg, None, block_expert, padded_counts)
    u = _esmm_any(impl, False, xs, wu, None, block_expert, padded_counts)
    h, h_vjp = jax.vjp(lambda g_, u_: act_fn(g_) * u_, g, u)
    # t = dys_w @ Wd[e]^T serves both dh (scaled by gate) and d_gate
    # (contracted against h): ys itself is never rebuilt.
    t = _esmm_any(impl, True, dys_w, wd, None, block_expert, padded_counts)
    d_gate = jnp.sum(
        t.astype(jnp.float32) * h.astype(jnp.float32), axis=-1
    )
    gate = row_gate[:, None].astype(dys_w.dtype)
    dys = dys_w * gate
    dg, du = h_vjp((t * gate).astype(h.dtype))
    dwd, _ = _esfk_any(impl, fused, h, dys, block_expert, padded_counts, False)
    dwg, _ = _esfk_any(impl, fused, xs, dg, block_expert, padded_counts, False)
    dwu, _ = _esfk_any(impl, fused, xs, du, block_expert, padded_counts, False)
    dxs = (
        _esmm_any(impl, True, dg, wg, None, block_expert, padded_counts)
        + _esmm_any(impl, True, du, wu, None, block_expert, padded_counts)
    )
    return (_scatter_dx(x, row_token, dxs), None,
            d_gate.astype(row_gate.dtype), None, None,
            dwg.astype(wg.dtype), dwu.astype(wu.dtype), dwd.astype(wd.dtype))


_esffn_glu.defvjp(_esffn_glu_fwd, _esffn_glu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _esffn_mlp(impl, act, x, row_token, row_gate, block_expert,
               padded_counts, w1, b1, w2, b2):
    return _esffn_fwd_any(
        impl, act, False, x, row_token, row_gate, block_expert,
        padded_counts, (w1, b1, w2, b2),
    )


def _esffn_mlp_fwd(impl, act, x, row_token, row_gate, block_expert,
                   padded_counts, w1, b1, w2, b2):
    y = _esffn_fwd_any(
        impl, act, False, x, row_token, row_gate, block_expert,
        padded_counts, (w1, b1, w2, b2),
    )
    return y, (x, row_token, row_gate, block_expert, padded_counts,
               w1, b1, w2, b2)


def _esffn_mlp_bwd(impl, act, res, dys_w):
    x, row_token, row_gate, block_expert, padded_counts, w1, b1, w2, b2 = res
    act_fn = ACTIVATIONS[act]
    fused = _FUSED_BACKWARD
    xs = _gather_rows(x, row_token)
    z = _esmm_any(impl, False, xs, w1, b1, block_expert, padded_counts)
    h, act_vjp = jax.vjp(act_fn, z)
    t = _esmm_any(impl, True, dys_w, w2, None, block_expert, padded_counts)
    # d_gate[r] = dys_w[r]·ys[r] with ys = h@W2 + b2 — split so ys is never
    # rebuilt: the h@W2 term contracts t against h, the b2 term is direct.
    d_gate = jnp.sum(
        t.astype(jnp.float32) * h.astype(jnp.float32), axis=-1
    )
    if b2 is not None:
        blk = xs.shape[0] // block_expert.shape[0]
        b2_rows = b2[jnp.repeat(block_expert, blk)]
        d_gate = d_gate + jnp.sum(
            dys_w.astype(jnp.float32) * b2_rows.astype(jnp.float32), axis=-1
        )
    gate = row_gate[:, None].astype(dys_w.dtype)
    dys = dys_w * gate
    (dz,) = act_vjp((t * gate).astype(h.dtype))
    dw2, db2 = _esfk_any(
        impl, fused, h, dys, block_expert, padded_counts, b2 is not None
    )
    dw1, db1 = _esfk_any(
        impl, fused, xs, dz, block_expert, padded_counts, b1 is not None
    )
    dxs = _esmm_any(impl, True, dz, w1, None, block_expert, padded_counts)
    return (_scatter_dx(x, row_token, dxs), None,
            d_gate.astype(row_gate.dtype), None, None,
            dw1.astype(w1.dtype),
            db1.astype(b1.dtype) if b1 is not None else None,
            dw2.astype(w2.dtype),
            db2.astype(b2.dtype) if b2 is not None else None)


_esffn_mlp.defvjp(_esffn_mlp_fwd, _esffn_mlp_bwd)


# ---------------------------------------------------------------------------
# quantized fused expert FFN (DESIGN.md §8): int8/fp8 payloads + block-wise
# scales flow through the same fused forward (VMEM dequant in the Pallas
# kernel, per-block dequant in the blocked XLA region). Backward recomputes
# the hidden through the QUANTIZED esmm ops (the quantized bytes are what
# move there too) and flows dX / d_gate / bias grads; the payloads and
# scales are frozen — training quantization is the STE fake_quant on the
# full-precision masters, not gradients into int8.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _esffn_glu_q(impl, act, x, row_token, row_gate, block_expert,
                 padded_counts, wg, sg, wu, su, wd, sd):
    return _esffn_fwd_any(
        impl, act, True, x, row_token, row_gate, block_expert,
        padded_counts, (wg, wu, wd), scales=(sg, su, sd),
    )


def _esffn_glu_q_fwd(impl, act, x, row_token, row_gate, block_expert,
                     padded_counts, wg, sg, wu, su, wd, sd):
    y = _esffn_fwd_any(
        impl, act, True, x, row_token, row_gate, block_expert,
        padded_counts, (wg, wu, wd), scales=(sg, su, sd),
    )
    return y, (x, row_token, row_gate, block_expert, padded_counts,
               wg, sg, wu, su, wd, sd)


def _esffn_glu_q_bwd(impl, act, res, dys_w):
    x, row_token, row_gate, block_expert, padded_counts, \
        wg, sg, wu, su, wd, sd = res
    act_fn = ACTIVATIONS[act]
    xs = _gather_rows(x, row_token)
    g = _esmm_any(impl, False, xs, wg, None, block_expert, padded_counts,
                  w_scales=sg)
    u = _esmm_any(impl, False, xs, wu, None, block_expert, padded_counts,
                  w_scales=su)
    h, h_vjp = jax.vjp(lambda g_, u_: act_fn(g_) * u_, g, u)
    t = _esmm_any(impl, True, dys_w, wd, None, block_expert, padded_counts,
                  w_scales=sd)
    d_gate = jnp.sum(t.astype(jnp.float32) * h.astype(jnp.float32), axis=-1)
    gate = row_gate[:, None].astype(dys_w.dtype)
    dg, du = h_vjp((t * gate).astype(h.dtype))
    dxs = (
        _esmm_any(impl, True, dg, wg, None, block_expert, padded_counts,
                  w_scales=sg)
        + _esmm_any(impl, True, du, wu, None, block_expert, padded_counts,
                    w_scales=su)
    )
    return (_scatter_dx(x, row_token, dxs), None,
            d_gate.astype(row_gate.dtype), None, None,
            _zero_cot(wg), jnp.zeros_like(sg),
            _zero_cot(wu), jnp.zeros_like(su),
            _zero_cot(wd), jnp.zeros_like(sd))


_esffn_glu_q.defvjp(_esffn_glu_q_fwd, _esffn_glu_q_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _esffn_mlp_q(impl, act, x, row_token, row_gate, block_expert,
                 padded_counts, w1, s1, b1, w2, s2, b2):
    return _esffn_fwd_any(
        impl, act, False, x, row_token, row_gate, block_expert,
        padded_counts, (w1, b1, w2, b2), scales=(s1, s2),
    )


def _esffn_mlp_q_fwd(impl, act, x, row_token, row_gate, block_expert,
                     padded_counts, w1, s1, b1, w2, s2, b2):
    y = _esffn_fwd_any(
        impl, act, False, x, row_token, row_gate, block_expert,
        padded_counts, (w1, b1, w2, b2), scales=(s1, s2),
    )
    return y, (x, row_token, row_gate, block_expert, padded_counts,
               w1, s1, b1, w2, s2, b2)


def _esffn_mlp_q_bwd(impl, act, res, dys_w):
    x, row_token, row_gate, block_expert, padded_counts, \
        w1, s1, b1, w2, s2, b2 = res
    act_fn = ACTIVATIONS[act]
    xs = _gather_rows(x, row_token)
    z = _esmm_any(impl, False, xs, w1, b1, block_expert, padded_counts,
                  w_scales=s1)
    h, act_vjp = jax.vjp(act_fn, z)
    t = _esmm_any(impl, True, dys_w, w2, None, block_expert, padded_counts,
                  w_scales=s2)
    d_gate = jnp.sum(t.astype(jnp.float32) * h.astype(jnp.float32), axis=-1)
    if b2 is not None:
        blk = xs.shape[0] // block_expert.shape[0]
        b2_rows = b2[jnp.repeat(block_expert, blk)]
        d_gate = d_gate + jnp.sum(
            dys_w.astype(jnp.float32) * b2_rows.astype(jnp.float32), axis=-1
        )
    gate = row_gate[:, None].astype(dys_w.dtype)
    dys = dys_w * gate
    (dz,) = act_vjp((t * gate).astype(h.dtype))
    # Biases stay full precision, so their grads flow normally.
    db1 = (ess(dz, block_expert, padded_counts, impl=impl).astype(b1.dtype)
           if b1 is not None else None)
    db2 = (ess(dys, block_expert, padded_counts, impl=impl).astype(b2.dtype)
           if b2 is not None else None)
    dxs = _esmm_any(impl, True, dz, w1, None, block_expert, padded_counts,
                    w_scales=s1)
    return (_scatter_dx(x, row_token, dxs), None,
            d_gate.astype(row_gate.dtype), None, None,
            _zero_cot(w1), jnp.zeros_like(s1), db1,
            _zero_cot(w2), jnp.zeros_like(s2), db2)


_esffn_mlp_q.defvjp(_esffn_mlp_q_fwd, _esffn_mlp_q_bwd)


def esffn_glu(
    x: jax.Array,
    row_token: jax.Array,
    row_gate: jax.Array,
    block_expert: jax.Array,
    padded_counts: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    scales=None,
    act: str = "silu",
    impl: Optional[str] = None,
) -> jax.Array:
    """Differentiable fused GLU expert FFN over the sorted layout.

    x: (N, D) UNSORTED tokens; row maps from ``core.reindex.build_reindex``.
    Returns the gate-weighted sorted output (Np, D) — combine it with
    ``core.reindex.scatter_rows``. ``scales``: (sg, su, sd) block-wise
    scales of quantized weights (DESIGN.md §8); dequant fuses into the
    kernels and the payloads are frozen (dX/d_gate grads only).
    """
    impl = impl or get_default_impl()
    if scales is not None:
        sg, su, sd = scales
        return _esffn_glu_q(impl, act, x, row_token, row_gate, block_expert,
                            padded_counts, w_gate, sg, w_up, su, w_down, sd)
    return _esffn_glu(impl, act, x, row_token, row_gate, block_expert,
                      padded_counts, w_gate, w_up, w_down)


def esffn_mlp(
    x: jax.Array,
    row_token: jax.Array,
    row_gate: jax.Array,
    block_expert: jax.Array,
    padded_counts: jax.Array,
    w1: jax.Array,
    b1: Optional[jax.Array],
    w2: jax.Array,
    b2: Optional[jax.Array],
    *,
    scales=None,
    act: str = "gelu",
    impl: Optional[str] = None,
) -> jax.Array:
    """Differentiable fused 2-MLP expert FFN; see ``esffn_glu``.
    ``scales``: (s1, s2) for quantized w1/w2 (biases full precision)."""
    impl = impl or get_default_impl()
    if scales is not None:
        s1, s2 = scales
        return _esffn_mlp_q(impl, act, x, row_token, row_gate, block_expert,
                            padded_counts, w1, s1, b1, w2, s2, b2)
    return _esffn_mlp(impl, act, x, row_token, row_gate, block_expert,
                      padded_counts, w1, b1, w2, b2)
