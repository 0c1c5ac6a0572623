"""Causal flash attention (Pallas TPU) — beyond-paper optimization.

The paper's kernels cover the MoE FFN; the roofline analysis
(EXPERIMENTS.md §Perf) shows the remaining HBM-bytes hot-spot is the
attention softmax transients that the pure-XLA stand-in materialises
between its two dots. This kernel keeps the (q_block x kv_block) logits
and probabilities in VMEM — HBM traffic collapses to q/k/v in + out once.

Layout: q (B, Hq, S, hd), k/v (B, Hkv, S, hd) — batch*head on the grid's
outer (parallel) axes, kv blocks innermost with a running (m, l, acc)
scratch. Causal masking at block granularity; fully-masked blocks are
skipped with pl.when (their DMA still runs; compute does not).

Validated in interpret mode against models.attention.chunked_attention
(tests/test_flash_kernel.py); ops-level wrapper handles GQA by folding the
group into the query head dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import pallas_interpret_default

NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale, bq, bk, causal):
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # kv block
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks strictly above the diagonal contribute nothing
    run = (not causal) or (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)       # (bq, hd)
        k = k_ref[0].astype(jnp.float32)       # (bk, hd)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                               # (bq, bk)
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            logits = jnp.where(kpos <= qpos, logits, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nj - 1)
    def _done():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def flash_attention_pallas(
    q: jax.Array,       # (BH, S, hd)
    k: jax.Array,       # (BH, S, hd)
    v: jax.Array,       # (BH, S, hd)
    *,
    causal: bool = True,
    bq: int = 512,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = pallas_interpret_default()
    bh, s, hd = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0
    scale = hd ** -0.5
    grid = (bh, s // bq, s // bk)

    flops = 4 * bh * s * s * hd * (0.5 if causal else 1.0)
    bytes_accessed = (
        q.size * q.dtype.itemsize * (s // bk)  # q re-read per kv block col?
        + 2 * k.size * k.dtype.itemsize
        + q.size * q.dtype.itemsize
    )

    return pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, bq=bq, bk=bk, causal=causal
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(flops), bytes_accessed=int(bytes_accessed),
            transcendentals=int(bh * s * s * (0.5 if causal else 1.0)),
        ),
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, **kw):
    """GQA wrapper: q (B,S,Hq,hd), k/v (B,S,Hkv,hd) -> (B,S,Hq,hd)."""
    b, s, hq, hd = q.shape
    _, _, hkv, _ = k.shape
    g = hq // hkv
    kr = jnp.repeat(k, g, axis=2) if g > 1 else k
    vr = jnp.repeat(v, g, axis=2) if g > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, hd)
    kf = kr.transpose(0, 2, 1, 3).reshape(b * hq, s, hd)
    vf = vr.transpose(0, 2, 1, 3).reshape(b * hq, s, hd)
    out = flash_attention_pallas(qf, kf, vf, causal=causal, **kw)
    return out.reshape(b, hq, s, hd).transpose(0, 2, 1, 3)
