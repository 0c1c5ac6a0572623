"""Paged decode attention (Pallas TPU) over a shared KV page pool. DESIGN.md §7.

Serving keeps one query row per slot and its keys/values scattered across
fixed-size pages of a shared pool (``models.lm.init_paged_cache``). This
kernel gathers them page-wise: the grid is (slot, kv-head, logical-page) and
the K/V BlockSpec index maps read the *scalar-prefetched* page table, so
each program pulls one head of one physical page into VMEM and folds it
into a running online softmax. Pages past the slot's length (and, for windowed
layers, pages wholly behind the window) never run; HBM traffic is therefore
proportional to the tokens actually resident, not to the dense
``num_slots x max_seq`` rectangle the old cache allocated up front.

Three implementations share the signature:

  * ``paged_attention_pallas``  — the kernel (interpret-mode off-TPU).
  * ``paged_attention_blocked`` — XLA fallback: a ``lax.scan`` over logical
    pages with the same online-softmax accumulator; one (B, page) block of
    K/V is gathered per step, so live memory stays page-bounded.
  * ``paged_attention_ref``     — gather the page table to a dense
    (B, maxp*page) view and run plain masked softmax attention; the
    numerical reference (same reduction structure as
    ``models.attention.decode_attention``) and the serving default on CPU.

``paged_attn_cost`` is the pricing entry ``parallel.autotune`` uses: its
bytes-accessed term sums ``ceil(len_i / page) * page`` over slots — by
construction there is no dense ``num_slots * max_seq`` term.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import cdiv, pallas_interpret_default

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# cost model (autotune pricing entry)
# ---------------------------------------------------------------------------

def paged_attn_cost(
    lengths: Sequence[int],
    page: int,
    hq: int,
    hkv: int,
    hd: int,
    itemsize: int = 2,
) -> dict:
    """Bytes/FLOPs of one paged decode-attention step for actual ``lengths``.

    bytes_accessed = q + out + the K/V pages that hold live tokens
    (``ceil(len/page) * page`` rows per slot). The dense layout's
    ``num_slots * max_seq`` rectangle never appears: an empty slot costs one
    query row, a short sequence costs its own pages only.
    """
    b = len(lengths)
    kv_rows = sum(cdiv(int(l), page) * page for l in lengths)
    tokens = sum(int(l) for l in lengths)
    q_bytes = b * hq * hd * itemsize
    kv_bytes = 2 * kv_rows * hkv * hd * itemsize
    flops = 4 * tokens * hq * hd  # qk^T + pv per live token, per q head
    return {
        "flops": int(flops),
        "bytes_accessed": int(q_bytes * 2 + kv_bytes),
        "transcendentals": int(tokens * hq),
    }


# ---------------------------------------------------------------------------
# reference: gather pages dense, plain masked softmax
# ---------------------------------------------------------------------------

def _mask_from(lengths, s, window):
    kpos = jnp.arange(s)[None, :]                        # (1, S)
    valid = kpos < lengths[:, None]                      # (B, S)
    if window is not None:
        valid &= kpos >= (lengths[:, None] - window)
    return valid


def paged_attention_ref(
    q: jax.Array,           # (B, 1, Hq, hd)
    k_pool: jax.Array,      # (npages, page, Hkv, hd)
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, maxp) int32, physical page per logical page
    lengths: jax.Array,     # (B,) int32, live tokens per slot (incl. current)
    *,
    k_scale: Optional[jax.Array] = None,  # (npages, page, Hkv) int8 pools
    v_scale: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Gather-dense paged decode attention (serving default off-TPU).

    Reconstructs each slot's logical (maxp*page) K/V view with one
    ``take`` over the page table, then runs the exact masked-softmax
    reduction of ``models.attention.decode_attention`` — the numerical
    reference the parity matrix pins the other impls against.
    ``k_scale``/``v_scale``: per-(row, kv-head) scales of int8 pools
    (DESIGN.md §8) — gathered pages are dequantized before the reduction.
    """
    b, one, hq, hd = q.shape
    npages, page, hkv, _ = k_pool.shape
    maxp = page_table.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else hd ** -0.5
    s = maxp * page

    def view(pool, sc):
        gathered = pool[page_table]                      # (B, maxp, page, Hkv, hd)
        if sc is not None:
            gathered = (gathered.astype(jnp.float32)
                        * sc[page_table][..., None]).astype(q.dtype)
        return gathered.reshape(b, s, hkv, hd)

    k_v = view(k_pool, k_scale)
    v_v = view(v_pool, v_scale)
    qg = q.reshape(b, hkv, g, hd)
    logits = jnp.einsum(
        "bhgd,bshd->bhgs", qg, k_v, preferred_element_type=jnp.float32
    ) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    valid = _mask_from(lengths, s, window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    # all-masked rows (empty slots) give a uniform p; zero them explicitly
    p = jnp.where(lengths[:, None, None, None] > 0, p, 0.0)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(v_v.dtype), v_v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# blocked: online softmax over logical pages (pure XLA)
# ---------------------------------------------------------------------------

def paged_attention_blocked(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Flash-decode over pages: scan logical pages, gather one physical
    (B, page) K/V block per step, fold into a running (m, l, acc). Live
    memory is one page per slot instead of the whole gathered view.
    int8 pools (``k_scale``/``v_scale``) dequantize per gathered page."""
    b, one, hq, hd = q.shape
    npages, page, hkv, _ = k_pool.shape
    maxp = page_table.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(b, hkv, g, hd)

    def step(carry, j):
        m, l, acc = carry
        phys = page_table[:, j]                          # (B,)
        kb = k_pool[phys]                                # (B, page, Hkv, hd)
        vb = v_pool[phys]
        if k_scale is not None:
            kb = (kb.astype(jnp.float32)
                  * k_scale[phys][..., None]).astype(q.dtype)
        if v_scale is not None:
            vb = (vb.astype(jnp.float32)
                  * v_scale[phys][..., None]).astype(q.dtype)
        logits = jnp.einsum(
            "bhgd,bphd->bhgp", qg, kb, preferred_element_type=jnp.float32
        ) * scale
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        kpos = j * page + jnp.arange(page)[None, :]      # (1, page)
        valid = kpos < lengths[:, None]
        if window is not None:
            valid &= kpos >= (lengths[:, None] - window)
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        # all-masked blocks (empty slot): exp(NEG_INF - NEG_INF) would be 1
        p = jnp.where(
            valid[:, None, None, :], jnp.exp(logits - m_new[..., None]), 0.0
        )
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgp,bphd->bhgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(maxp))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# pallas kernel
# ---------------------------------------------------------------------------

def _paged_kernel(
    pt_ref,      # scalar prefetch (B, maxp) int32
    len_ref,     # scalar prefetch (B,) int32
    q_ref,       # (1, 1, G, hd)
    k_ref,       # (1, page, hd) — one head of a physical page (pt_ref map)
    v_ref,
    *rest,       # [ks (1, page, Hkv), vs] o_ref, m_s, l_s, acc_s
    scale: float,
    page: int,
    window: Optional[int],
    softcap: float,
    quantized: bool,
):
    rest = list(rest)
    ks_ref = rest.pop(0) if quantized else None
    vs_ref = rest.pop(0) if quantized else None
    o_ref, m_s, l_s, acc_s = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    def head_scale(s_ref):
        # (page, Hkv) scales of the page -> this head's (page, 1) column.
        sc = s_ref[0]
        col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        return jnp.sum(jnp.where(col == h, sc, 0.0), axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[b]
    run = j * page < length
    if window is not None:
        run &= (j + 1) * page > length - window  # page wholly behind window

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)              # (G, hd)
        k = k_ref[0].astype(jnp.float32)                 # (page, hd)
        if quantized:
            # per-row dequant of the gathered int8 page (DESIGN.md §8)
            k = k * head_scale(ks_ref)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # (G, page)
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        gdim = logits.shape[0]
        kpos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (gdim, page), 1
        )
        valid = kpos < length
        if window is not None:
            valid &= kpos >= length - window
        logits = jnp.where(valid, logits, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_s[...] = l_s[...] * alpha + p.sum(axis=1, keepdims=True)
        m_s[...] = m_new
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            v = v * head_scale(vs_ref)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nj - 1)
    def _done():
        o_ref[0, 0] = (
            acc_s[...] / jnp.maximum(l_s[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "interpret")
)
def paged_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: float = 0.0,
    interpret: bool | None = None,
) -> jax.Array:
    """One query row per slot, K/V gathered page-wise through the
    scalar-prefetched page table (grid = slot x kv-head x logical page).
    int8 pools ride with per-(row, head) scale pools whose pages follow
    the same table-indexed BlockSpec and dequantize in VMEM
    (DESIGN.md §8) — the int8 bytes are what cross HBM."""
    if interpret is None:
        interpret = pallas_interpret_default()
    b, one, hq, hd = q.shape
    npages, page, hkv, _ = k_pool.shape
    maxp = page_table.shape[1]
    g = hq // hkv
    scale = hd ** -0.5
    qg = q.reshape(b, hkv, g, hd)
    grid = (b, hkv, maxp)
    quantized = k_scale is not None

    cost = paged_attn_cost(
        [maxp * page] * b, page, hq, hkv, hd, k_pool.dtype.itemsize
    )

    # The pools are read as (npages, page, Hkv*hd), a free reshape: one
    # head's (page, hd) tile is then a block over whole lanes, which the
    # Mosaic (8, 128) block rule accepts (a (1, hd) window over the
    # (Hkv, hd) minor dims is refused). Scales come a page at a time.
    kv_spec = pl.BlockSpec(
        (1, page, hd), lambda bb, h, j, pt, ln: (pt[bb, j], 0, h)
    )
    in_specs = [
        pl.BlockSpec((1, 1, g, hd), lambda bb, h, j, pt, ln: (bb, h, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    args = [qg, k_pool.reshape(npages, page, hkv * hd),
            v_pool.reshape(npages, page, hkv * hd)]
    if quantized:
        sc_spec = pl.BlockSpec(
            (1, page, hkv), lambda bb, h, j, pt, ln: (pt[bb, j], 0, 0)
        )
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]

    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, page=page, window=window,
            softcap=softcap, quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, g, hd), lambda bb, h, j, pt, ln: (bb, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=cost["flops"],
            bytes_accessed=cost["bytes_accessed"],
            transcendentals=cost["transcendentals"],
        ),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), *args)
    return out.reshape(b, 1, hq, hd)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: float = 0.0,
    impl: Optional[str] = None,
) -> jax.Array:
    """Impl dispatch, mirroring ``kernels.ops``: "pallas" | "blocked" |
    "ref" (the gather-dense reference — on CPU the page gather is
    memory-bound either way and the dense reduction is what the parity
    matrix pins). ``k_scale``/``v_scale``: int8-pool
    per-(row, head) scales (DESIGN.md §8)."""
    from repro.kernels import ops

    impl = impl or ops.get_default_impl()
    kw = dict(k_scale=k_scale, v_scale=v_scale, window=window,
              softcap=softcap)
    if impl == "pallas":
        return paged_attention_pallas(q, k_pool, v_pool, page_table,
                                      lengths, **kw)
    if impl == "blocked":
        return paged_attention_blocked(q, k_pool, v_pool, page_table,
                                       lengths, **kw)
    if impl == "ref":
        return paged_attention_ref(q, k_pool, v_pool, page_table,
                                   lengths, **kw)
    raise ValueError(f"unknown paged attention impl {impl!r}")
