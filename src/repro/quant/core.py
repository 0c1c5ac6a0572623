"""Block-wise expert-weight and KV quantization (DESIGN.md §8).

One rounding/clipping convention for the whole repo:

  q = clip(round(x / scale), -Q, Q)        scale = amax(block) / Q

with symmetric ranges (int8: Q = 127; fp8-e4m3: Q = 448, the format's
finite max — the "round" is the cast's round-to-nearest). Scales are
float32, one per *block*:

  * expert weights — one scale per ``(expert, tile_row, tile_col)`` block of
    the trailing two dims (leading dims — period stacking, the expert dim —
    are batch). Blocks default to 128x128 (clamped to the dim), so a scale
    tile always nests inside the Pallas kernels' weight BlockSpecs and the
    in-VMEM dequant is a reshape-broadcast-multiply (DESIGN.md §8).
  * KV rows — one scale per written ``(token-row, kv-head)``: each decode
    step quantizes only the row it writes, so page contents never need
    re-scaling (``quantize_rows`` / ``dequantize_rows``).

Training uses the straight-through estimator: ``fake_quant`` runs the real
quantize→dequantize in forward and passes gradients through unchanged
(``custom_vjp`` identity), so routers/dense layers — which are never
quantized — and the expert master weights all keep full-precision grads.
``quantize_blockwise(..., rng=...)`` optionally applies stochastic rounding
(floor(x/scale + u), u ~ U[0,1)) so QAT rounding is unbiased in expectation.

The gradient-compression helpers ``quantize_int8``/``dequantize_int8``
(single shared scale, the ``optim.compression`` error-feedback path) live
here too and are re-exported by ``optim.compression`` — one convention,
one module.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

#: Supported quantized-weight formats -> (storage dtype, symmetric max).
QUANT_FORMATS = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}

#: Expert-weight keys the param walkers quantize (routers/norms/biases
#: always stay full precision).
EXPERT_WEIGHT_KEYS = ("w_gate", "w_up", "w_down", "w1", "w2")


def quant_bits(mode: Optional[str]) -> int:
    """Storage bits per weight element for a quant mode (16 for none —
    the bf16 baseline the autotune byte model prices against)."""
    if mode in (None, "none"):
        return 16
    if mode not in QUANT_FORMATS:
        raise ValueError(f"unknown quant mode {mode!r}")
    return 8


# ---------------------------------------------------------------------------
# gradient-compression convention (moved from optim.compression)
# ---------------------------------------------------------------------------

def quantize_int8(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Symmetric int8 with a caller-supplied (shared) scale — the
    collective-safe form ``optim.compression.compressed_psum`` needs (the
    scale is agreed across the group before payloads move)."""
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-30))
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of ``quantize_int8`` (float32 out)."""
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# block-wise weight quantization
# ---------------------------------------------------------------------------

def block_tiles(shape: Sequence[int], tile: int) -> tuple[int, int]:
    """Per-axis tile sizes over the trailing two dims: ``tile`` clamped to
    the dim (a dim smaller than the tile is one block). Dims larger than
    the tile must divide evenly — weight shapes here are MXU-aligned."""
    a, b = int(shape[-2]), int(shape[-1])
    ta, tb = min(tile, a), min(tile, b)
    if a % ta or b % tb:
        raise ValueError(f"dims {(a, b)} not divisible by tiles {(ta, tb)}")
    return ta, tb


def _upsample(scales: jax.Array, shape: Sequence[int]) -> jax.Array:
    """Broadcast per-block scales up to the full weight shape."""
    *batch, a, b = shape
    na, nb = scales.shape[-2:]
    s = scales.reshape(*scales.shape[:-2], na, 1, nb, 1)
    s = jnp.broadcast_to(
        s, tuple(scales.shape[:-2]) + (na, a // na, nb, b // nb)
    )
    return s.reshape(tuple(shape))


def quantize_blockwise(
    w: jax.Array,
    *,
    mode: str = "int8",
    tile: int = 128,
    rng: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Quantize ``w`` block-wise over its trailing two dims.

    Returns ``(q, scales)`` with ``q`` int8/fp8-e4m3 shaped like ``w`` and
    ``scales`` float32 shaped ``(*batch, A/tile_a, B/tile_b)``. ``rng``
    enables stochastic rounding (int8 only): ``floor(x/scale + u)`` with
    ``u ~ U[0,1)``, unbiased in expectation — the training-side option.
    """
    if mode not in QUANT_FORMATS:
        raise ValueError(f"unknown quant mode {mode!r}")
    dtype, qmax = QUANT_FORMATS[mode]
    ta, tb = block_tiles(w.shape, tile)
    *batch, a, b = w.shape
    wf = w.astype(jnp.float32)
    blocks = wf.reshape(*batch, a // ta, ta, b // tb, tb)
    amax = jnp.max(jnp.abs(blocks), axis=(-3, -1))
    scales = (jnp.maximum(amax, 1e-30) / qmax).astype(jnp.float32)
    x = wf / _upsample(scales, w.shape)
    if mode == "int8":
        if rng is not None:
            x = jnp.floor(x + jax.random.uniform(rng, x.shape))
        else:
            x = jnp.round(x)
        q = jnp.clip(x, -qmax, qmax).astype(dtype)
    else:
        if rng is not None:
            raise ValueError("stochastic rounding is int8-only")
        q = jnp.clip(x, -qmax, qmax).astype(dtype)
    return q, scales


def dequantize_blockwise(
    q: jax.Array, scales: jax.Array, dtype: Any = jnp.float32
) -> jax.Array:
    """Inverse of ``quantize_blockwise``; tile sizes are inferred from the
    q/scales shapes. Exact for values representable on the block's grid."""
    return (q.astype(jnp.float32) * _upsample(scales, q.shape)).astype(dtype)


def scale_block_dims(wdims, sdims, bdims) -> tuple:
    """Block dims of a scale operand congruent with its weight BlockSpec.

    For each trailing weight axis (full extent ``wdims``, ``sdims`` scale
    blocks, kernel block ``bdims``) the per-axis quant tile
    ``wdim // sdim`` must divide the kernel block; the scale tile then
    covers ``bdim // tile`` blocks. Shared by the esmm/esffn kernels so
    the scale-layout contract has one implementation (DESIGN.md §8)."""
    out = []
    for d, s, b in zip(wdims, sdims, bdims):
        t = d // s
        if b % t:
            raise ValueError(
                f"quant tile {t} does not divide kernel block {b} "
                f"(dim {d}, {s} scale blocks)"
            )
        out.append(b // t)
    return tuple(out)


def tile_scales(scales: jax.Array, wdims, bdims) -> jax.Array:
    """Lay a weight's ``(E, nA, nB)`` scales out as ``(E, nA/sa, nB/sb, sa,
    sb)``, one ``(sa, sb)`` tile per ``(A, B)`` kernel block of the weight.

    The kernels index that operand with a ``(1, 1, 1, sa, sb)`` block: its
    last two dims are whole, which the Mosaic (8, 128) block rule accepts
    where a ``(1, sa, sb)`` window of the plain scales is refused."""
    sa, sb = scale_block_dims(wdims, scales.shape[-2:], bdims)
    e, na, nb = scales.shape
    return scales.reshape(e, na // sa, sa, nb // sb, sb).transpose(0, 1, 3, 2, 4)


def dequant_tile(w: jax.Array, s: jax.Array) -> jax.Array:
    """In-kernel VMEM dequant of one 2-D weight tile (DESIGN.md §8).

    ``w``: (A, B) int8/fp8 tile as loaded by the kernel's BlockSpec; ``s``:
    the congruent (na, nb) scale tile — each scale covers an
    (A/na, B/nb) sub-block. Returns float32 (A, B), fed straight to the
    MXU contraction; the quantized bytes are all that crossed HBM.
    """
    a, b = w.shape
    na, nb = s.shape
    wf = w.astype(jnp.float32)
    s = s.astype(jnp.float32)
    if nb > 1:  # widen the scales along lanes: (na, nb) -> (na, B)
        s = jnp.broadcast_to(s[:, :, None], (na, nb, b // nb)).reshape(na, b)
    if na > 1:  # then along sublanes: (na, B) -> (A, B)
        s = jnp.broadcast_to(s[:, None, :], (na, a // na, b)).reshape(a, b)
    return wf * s


# ---------------------------------------------------------------------------
# straight-through estimator (training / QAT)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _fake_quant(w, mode, tile):
    q, s = quantize_blockwise(w, mode=mode, tile=tile)
    return dequantize_blockwise(q, s, dtype=w.dtype)


def _fake_quant_fwd(w, mode, tile):
    return _fake_quant(w, mode, tile), None


def _fake_quant_bwd(mode, tile, _, g):
    return (g,)  # straight-through: d(dequant∘quant)/dw := identity


_fake_quant.defvjp(_fake_quant_fwd, _fake_quant_bwd)


def fake_quant(w: jax.Array, mode: str = "int8", tile: int = 128) -> jax.Array:
    """Quantize-dequantize with straight-through gradients (DESIGN.md §8).

    Forward runs the real block-wise round-trip (numerics match the
    deployed int8/fp8 weights); backward passes the cotangent through
    unchanged, so the full-precision master weights keep training while
    the loss sees quantized arithmetic. Routers and dense layers are
    simply never passed through this — their grads are untouched."""
    return _fake_quant(w, mode, tile)


# ---------------------------------------------------------------------------
# KV-row quantization (paged cache payloads, DESIGN.md §8)
# ---------------------------------------------------------------------------

def quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 over the trailing (head_dim) axis.

    ``x``: (..., hd) K or V rows about to be written to the paged pool.
    Returns (int8 rows, float32 scales shaped (...,)) — one scale per
    written (token-row, kv-head), so a decode step quantizes only its own
    row and already-resident pages never re-scale."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = (jnp.maximum(amax, 1e-30) / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_rows(
    q: jax.Array, scale: jax.Array, dtype: Any = jnp.float32
) -> jax.Array:
    """Inverse of ``quantize_rows`` (scale broadcasts over the row)."""
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


# ---------------------------------------------------------------------------
# parameter-tree walkers
# ---------------------------------------------------------------------------

def quantize_ffn(ffn: dict, *, mode: str = "int8", tile: int = 128) -> dict:
    """Quantize one MoE FFN param dict's expert weights in place-style.

    Each ``EXPERT_WEIGHT_KEYS`` leaf becomes its int8/fp8 payload plus a
    ``<name>_scale`` float32 entry; router and biases pass through. Leading
    dims (period stacking, the expert dim) are batch — scales are
    per-(expert, tile)."""
    out = dict(ffn)
    for name in EXPERT_WEIGHT_KEYS:
        w = ffn.get(name)
        if w is None or f"{name}_scale" in ffn:
            continue
        q, s = quantize_blockwise(w, mode=mode, tile=tile)
        out[name] = q
        out[f"{name}_scale"] = s
    return out


def ffn_scales(ffn: dict) -> Optional[dict]:
    """The ``<name>_scale`` entries of a (possibly) quantized FFN dict, or
    None when the dict holds plain full-precision weights."""
    s = {k: v for k, v in ffn.items() if k.endswith("_scale")}
    return s or None


def quantize_lm_params(
    params: dict, cfg, *, mode: str = "int8", tile: int = 128
) -> dict:
    """Quantize every MoE layer's expert weights in a full LM value tree
    (post-``split_tree``). Dense FFNs, attention, norms, embeddings and
    routers stay full precision — this is the serving-side true-quant
    entry (``launch/serve.py --quant``); training QAT goes through
    ``fake_quant`` inside the island instead."""
    from repro.models.lm import _ffn_kind  # lazy: avoid kernels<->models cycle

    out = dict(params)
    layers = []
    for pos, layer in enumerate(params["layers"]):
        if _ffn_kind(cfg, pos) == "moe" and "ffn" in layer:
            layer = dict(layer)
            layer["ffn"] = quantize_ffn(layer["ffn"], mode=mode, tile=tile)
        layers.append(layer)
    out["layers"] = layers
    return out
