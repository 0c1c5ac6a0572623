"""Attention primitives: RoPE, chunked-causal GQA attention, decode attention.

Training/prefill attention is *chunked-causal*: an unrolled outer loop over
query chunks where chunk c only reads K/V[0 : (c+1)*chunk] (static slice), so
the compiled FLOPs are ~half of a masked full-S^2 implementation and sliding
windows become genuinely sub-quadratic (chunk c reads a static window slice).
Within a chunk an online-softmax scan over KV blocks bounds live memory to
(chunk x kv_block) logits — the pure-XLA shape of flash attention
(DESIGN.md §2).

Decode attention is a plain einsum over the cache: O(S·d) memory-bound work
that GSPMD shards (sequence-sharded caches combine via partial-softmax
all-reduce — the flash-decode pattern, inserted by the partitioner).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common import cdiv

NEG_INF = -2.0e38


def rope(
    x: jax.Array, positions: jax.Array, theta: float = 1e4
) -> jax.Array:
    """Rotary embeddings. x: (B, S, H, hd); positions: (B, S) or (S,)."""
    b, s, h, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def _attn_block(q, k, qpos, kpos, *, causal, window, prefix_len, scale, softcap):
    """Masked logits for one (q-chunk, kv-block) pair."""
    # q: (B, cs, Hkv, G, hd); k: (B, bk, Hkv, hd)
    logits = jnp.einsum(
        "bqhgd,bkhd->bqhgk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    if causal:
        allowed = kpos[None, :] <= qpos[:, None]
        if window is not None:
            allowed &= kpos[None, :] > (qpos[:, None] - window)
        if prefix_len:
            allowed |= (kpos[None, :] < prefix_len) & (qpos[:, None] < prefix_len)
    else:
        allowed = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    return jnp.where(allowed[None, :, None, None, :], logits, NEG_INF)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    q_chunk: int = 2048,
    kv_block: int = 2048,
    scale: Optional[float] = None,
    softcap: float = 0.0,
) -> jax.Array:
    """GQA attention, sub-quadratic-aware. q: (B,S,Hq,hd); k/v: (B,S,Hkv,hd)."""
    b, s, hq, hd = q.shape
    _, skv, hkv, _ = k.shape
    assert s == skv, "prefill/train assumes aligned q and kv"
    g = hq // hkv
    scale = scale if scale is not None else hd ** -0.5
    q = q.reshape(b, s, hkv, g, hd)

    q_chunk = min(q_chunk, s)
    while s % q_chunk:
        q_chunk //= 2
    n_chunks = s // q_chunk

    @functools.partial(jax.checkpoint, prevent_cse=False, static_argnums=(3,))
    def run_chunk(q_c, k_c, v_c, meta):
        """One query chunk. Rematerialised in backward so per-chunk online-
        softmax residuals never accumulate across chunks (flash-attention
        memory structure, expressed as nested remat)."""
        c, start, span, bk = meta
        qpos = c * q_chunk + jnp.arange(q_chunk)
        kb = k_c.reshape(b, span // bk, bk, hkv, hd)
        vb = v_c.reshape(b, span // bk, bk, hkv, hd)
        kpos0 = start + jnp.arange(span).reshape(span // bk, bk)

        def step(carry, inp):
            m, l, acc = carry
            kblk, vblk, kpos = inp
            logits = _attn_block(
                q_c, kblk, qpos, kpos, causal=causal,
                window=window if causal else None,
                prefix_len=prefix_len, scale=scale, softcap=softcap,
            )
            m_new = jnp.maximum(m, logits.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p, vblk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, q_chunk, hkv, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, q_chunk, hkv, g), jnp.float32)
        a0 = jnp.zeros((b, q_chunk, hkv, g, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(step, prevent_cse=False),
            (m0, l0, a0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kpos0),
        )
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out_chunks = []
    for c in range(n_chunks):
        q_c = jax.lax.slice_in_dim(q, c * q_chunk, (c + 1) * q_chunk, axis=1)
        # Static KV range this chunk can see.
        end = (c + 1) * q_chunk if causal else s
        start = 0
        if causal and window is not None and not prefix_len:
            start = max(0, (c + 1) * q_chunk - window - q_chunk)
        span = end - start
        bk = min(kv_block, span)
        while span % bk:
            bk //= 2
        k_c = jax.lax.slice_in_dim(k, start, end, axis=1)
        v_c = jax.lax.slice_in_dim(v, start, end, axis=1)
        out_chunks.append(run_chunk(q_c, k_c, v_c, (c, start, span, bk)))

    out = jnp.concatenate(out_chunks, axis=1)
    return out.reshape(b, s, hq, hd).astype(q.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    scale: Optional[float] = None,
    softcap: float = 0.0,
) -> jax.Array:
    """Single-step decode. q: (B,1,Hq,hd); caches: (B,S,Hkv,hd).

    Positions >= cache_len are masked. Memory-bound: one pass over the
    cache; with a sequence-sharded cache GSPMD lowers the softmax into the
    flash-decode partial-reduction pattern.
    """
    b, one, hq, hd = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(b, hkv, g, hd)
    logits = jnp.einsum(
        "bhgd,bshd->bhgs", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    valid = jnp.arange(s)[None] < cache_len[:, None]  # (B, S)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, hq, hd).astype(q.dtype)
