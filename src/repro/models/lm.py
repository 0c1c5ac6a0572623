"""LM assembly: parameter init, forward pass (train/prefill/decode),
KV/state cache management, and loss.

Layers are grouped into *periods* (the lcm of the layer/attention/MoE
patterns); parameters are stacked over periods and the forward runs a
``lax.scan`` over periods with the blocks of one period unrolled inside.
This keeps the HLO size O(period) regardless of depth — essential for
72-layer stacks such as Jamba — and is where the remat policy (the paper's
pipeline-shared cache, DESIGN.md §2) is applied.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import frontends, mamba, transformer as tfm, xlstm
from repro.obs import device as obs_device
from repro.models.transformer import Ctx
from repro.parallel.sharding import (
    ParallelConfig,
    Param,
    constrain,
    normal_init,
    split_tree,
)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _ffn_kind(cfg: ModelConfig, idx: int) -> Optional[str]:
    kind = cfg.layer_kind(idx)
    if kind == "slstm":
        return "slstm_ffn"
    if kind == "mlstm":
        return None
    if cfg.is_moe_layer(idx):
        return "moe"
    if cfg.d_ff > 0:
        return "dense"
    return None


def init_block(key, cfg: ModelConfig, idx: int, dtype, plan=None) -> dict:
    """One block's parameters; ``plan`` (core.hetero.HeteroPlan) pads MoE
    FFN hidden dims for heterogeneous TP tiles (DESIGN.md §6)."""
    kind = cfg.layer_kind(idx)
    ks = jax.random.split(key, 6)
    p: dict = {"ln1": tfm.init_norm(cfg)}
    if kind == "attn":
        p["mixer"] = tfm.init_attention(ks[0], cfg, dtype)
    elif kind == "mamba":
        p["mixer"] = mamba.init_mamba(ks[0], cfg, dtype)
    elif kind == "mlstm":
        p["mixer"] = xlstm.init_mlstm(ks[0], cfg, dtype)
    elif kind == "slstm":
        p["mixer"] = xlstm.init_slstm(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if cfg.cross_attn and kind == "attn":
        p["ln_x"] = tfm.init_norm(cfg)
        p["xattn"] = tfm.init_cross_attention(ks[1], cfg, dtype)
    fk = _ffn_kind(cfg, idx)
    if fk is not None:
        p["ln2"] = tfm.init_norm(cfg)
        if fk == "moe":
            p["ffn"] = tfm.init_moe_ffn(ks[2], cfg, dtype, plan=plan)
        elif fk == "dense":
            p["ffn"] = tfm.init_dense_ffn(ks[2], cfg, dtype)
        else:  # slstm_ffn: small GLU
            f = int(cfg.xlstm.ffn_factor * cfg.d_model)
            f = (f + 63) // 64 * 64
            sub = dataclasses.replace(cfg, d_ff=f, glu=True)
            p["ffn"] = tfm.init_dense_ffn(ks[2], sub, dtype)
    return p


def init_params(key, cfg: ModelConfig, plan=None) -> dict:
    """Full parameter tree (Param leaves). eval_shape-safe.

    ``plan`` (core.hetero.HeteroPlan, DESIGN.md §6): Eq. 2 hidden splits pad
    every MoE FFN to per-TP-rank tiles; an even split changes nothing."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.num_layers + 4)
    period = cfg.period
    n_periods = cfg.num_layers // period

    layers = []
    for pos in range(period):
        per_period = [
            init_block(keys[pp * period + pos], cfg, pos, dtype, plan=plan)
            for pp in range(n_periods)
        ]
        stacked = jax.tree.map(
            lambda *xs: Param(
                jnp.stack([x.value for x in xs]),
                (None,) + xs[0].spec,
            ),
            *per_period,
            is_leaf=lambda x: isinstance(x, Param),
        )
        layers.append(stacked)

    p = {
        "embed": Param(
            normal_init(keys[-1], (cfg.vocab_size, cfg.d_model), dtype),
            ("tp", "fsdp"),
        ),
        "final_norm": tfm.init_norm(cfg),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        p["head"] = Param(
            normal_init(keys[-2], (cfg.d_model, cfg.vocab_size), dtype),
            ("fsdp", "tp"),
        )
    if cfg.num_codebooks > 1:
        p["cb_heads"] = Param(
            normal_init(
                keys[-2], (cfg.num_codebooks, cfg.d_model, cfg.vocab_size), dtype
            ),
            (None, "fsdp", "tp"),
        )
    if cfg.frontend:
        p["frontend"] = frontends.init_frontend(keys[-3], cfg, dtype)
    return p


def abstract_params(cfg: ModelConfig, plan=None) -> tuple[Any, Any]:
    """(ShapeDtypeStruct tree, logical spec tree) without allocating."""
    shapes = jax.eval_shape(
        lambda k: init_params(k, cfg, plan=plan), jax.random.PRNGKey(0)
    )
    # eval_shape maps over Param leaves; reconstruct specs from a concrete
    # tiny init of the STRUCTURE only: specs are static, rebuild via init on
    # the abstract tree (Param is a NamedTuple, eval_shape keeps it intact
    # with .spec as aux? no — spec is an array-free leaf). Simplest: call
    # init_params under eval_shape and read spec from the returned tree.
    values = jax.tree.map(
        lambda p: p.value, shapes, is_leaf=lambda x: isinstance(x, Param)
    )
    specs = jax.tree.map(
        lambda p: p.spec, shapes, is_leaf=lambda x: isinstance(x, Param)
    )
    return values, specs


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """Abstract decode cache (stacked over periods per position)."""
    dtype = jnp.dtype(cfg.dtype)
    period = cfg.period
    n_periods = cfg.num_layers // period
    layers = []
    for pos in range(period):
        kind = cfg.layer_kind(pos)
        if kind == "attn":
            spec = tfm.cache_spec_attention(cfg, pos, batch, seq_len, dtype)
        elif kind == "mamba":
            spec = mamba.cache_spec_mamba(cfg, batch, dtype)
        elif kind == "mlstm":
            spec = xlstm.cache_spec_mlstm(cfg, batch, dtype)
        elif kind == "slstm":
            spec = xlstm.cache_spec_slstm(cfg, batch)
        layers.append(
            jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (n_periods,) + s.shape, s.dtype
                ),
                spec,
            )
        )
    return {
        "layers": layers,
        "len": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    spec = cache_spec(cfg, batch, seq_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


# ---------------------------------------------------------------------------
# paged serving cache (DESIGN.md §7)
# ---------------------------------------------------------------------------

def paged_cache_spec(
    cfg: ModelConfig,
    num_slots: int,
    num_pages: int,
    page_size: int,
    kv_quant: Optional[str] = None,
) -> dict:
    """Abstract paged decode cache for serving (DESIGN.md §7).

    Attention layers trade the dense per-slot ``(num_slots, max_seq)``
    rectangle for a SHARED pool of ``num_pages`` fixed-size pages per
    period position: ``(n_periods, num_pages, page_size, Hkv, hd)``.
    Which slot owns which page lives host-side in the scheduler's page
    table, passed to the decode step as an input each macro-step —
    physical page 0 is reserved as the write sink for inactive slots and
    is never allocated. Windowed layers store full positions too (the
    window is masked at read; a rolling buffer would break page identity).

    ``kv_quant="int8"`` (DESIGN.md §8): the pools hold int8 payloads plus
    float32 ``k_scale``/``v_scale`` pools of per-(row, kv-head) scales —
    ~(itemsize*hd)/(hd+4)x smaller pages, so the same HBM budget admits
    proportionally more pages (``paged_kv_page_bytes``/``PagePool``).

    Recurrent mixers (mamba/xlstm) keep their per-slot constant-size state
    exactly as in ``cache_spec`` — there is nothing to page.
    """
    if kv_quant not in (None, "none", "int8"):
        raise ValueError(f"unsupported kv_quant {kv_quant!r}")
    quant = kv_quant == "int8"
    dtype = jnp.int8 if quant else jnp.dtype(cfg.dtype)
    period = cfg.period
    n_periods = cfg.num_layers // period
    pool = jax.ShapeDtypeStruct(
        (n_periods, num_pages, page_size, cfg.num_kv_heads, cfg.hd), dtype
    )
    sc_pool = jax.ShapeDtypeStruct(
        (n_periods, num_pages, page_size, cfg.num_kv_heads), jnp.float32
    )
    layers = []
    for pos in range(period):
        kind = cfg.layer_kind(pos)
        if kind == "attn":
            entry = {"k": pool, "v": pool}
            if quant:
                entry["k_scale"] = sc_pool
                entry["v_scale"] = sc_pool
            layers.append(entry)
            continue
        if kind == "mamba":
            spec = mamba.cache_spec_mamba(cfg, num_slots, dtype)
        elif kind == "mlstm":
            spec = xlstm.cache_spec_mlstm(cfg, num_slots, dtype)
        else:  # slstm
            spec = xlstm.cache_spec_slstm(cfg, num_slots)
        layers.append(
            jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (n_periods,) + s.shape, s.dtype
                ),
                spec,
            )
        )
    return {
        "layers": layers,
        "len": jax.ShapeDtypeStruct((num_slots,), jnp.int32),
    }


def paged_cache_logical_specs(cfg: ModelConfig, cache: dict) -> dict:
    """Logical partition specs for the paged cache tree: the shared page
    pool shards its PAGE dim over "dp" so the pool's bytes spread across
    data ranks; per-slot recurrent state and lengths shard the slot dim.

    Note the allocator (``parallel.cache.PagePool``) treats physical pages
    as fungible — a hetero group's share is a COUNT, not a contiguous page
    range, so on a real mesh a slot's pages land on arbitrary ranks and
    the page-wise gather crosses devices. Rank-local (range-partitioned)
    allocation is the natural next step for multi-host serving."""
    layers = []
    for pos in range(cfg.period):
        kind = cfg.layer_kind(pos)
        if kind == "attn":
            spec = {"k": (None, "dp", None, None, None),
                    "v": (None, "dp", None, None, None)}
            if "k_scale" in cache["layers"][pos]:
                spec["k_scale"] = (None, "dp", None, None)
                spec["v_scale"] = (None, "dp", None, None)
        elif kind == "mamba":
            spec = {"conv": (None, "dp", None, "tp"),
                    "ssm": (None, "dp", "tp", None)}
        elif kind == "mlstm":
            spec = {"c": (None, "dp", None, None, None),
                    "n": (None, "dp", None, None),
                    "m": (None, "dp", None),
                    "conv": (None, "dp", None, "tp")}
        else:  # slstm
            spec = {k: (None, "dp", None, None) for k in ("c", "n", "h", "m")}
        layers.append(spec)
    return {"layers": layers, "len": ("dp",)}


def init_paged_cache(
    cfg: ModelConfig,
    num_slots: int,
    num_pages: int,
    page_size: int,
    kv_quant: Optional[str] = None,
) -> dict:
    spec = paged_cache_spec(cfg, num_slots, num_pages, page_size,
                            kv_quant=kv_quant)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def paged_kv_page_bytes(
    cfg: ModelConfig, page_size: int, kv_quant: Optional[str] = None
) -> int:
    """HBM bytes ONE physical page costs across every attention layer —
    the unit ``parallel.cache.PagePool`` budgets admission in.

    With ``kv_quant="int8"`` each K/V row stores hd int8 payload bytes
    plus one float32 per-(row, head) scale, so a page costs
    ``(hd + 4) / (hd * itemsize)`` of its full-precision size and the
    same HBM admits proportionally more concurrent requests
    (DESIGN.md §8)."""
    n_attn = sum(cfg.layer_kind(i) == "attn" for i in range(cfg.num_layers))
    row = cfg.num_kv_heads * cfg.hd * jnp.dtype(cfg.dtype).itemsize
    if kv_quant == "int8":
        row = cfg.num_kv_heads * (cfg.hd + 4)  # int8 payload + f32 scale
    return n_attn * 2 * page_size * row


def reset_slot(cfg: ModelConfig, cache: dict, slot: int,
               length: int = 0) -> dict:
    """Reset one slot's length and zero its recurrent state so a new
    request can reuse it (continuous-batching slot refill). K/V needs no
    scrub: the dense buffer and freshly-granted pages are both masked by
    ``len``.

    ``length > 0`` is the prefix-sharing admission path (DESIGN.md §7):
    the slot starts with ``length`` tokens already resident — whole pages
    matched by the radix index and forked into the slot's page table at
    refcount+1 — so the next chunk-prefill continues at absolute position
    ``length`` instead of re-prefilling the shared prefix. The shared
    pages themselves MUST NOT be scrubbed here: other slots and the index
    still read them. Only valid for all-attention stacks (recurrent state
    is per-slot and cannot be borrowed page-wise)."""
    if length and any(cfg.layer_kind(p) != "attn" for p in range(cfg.period)):
        raise ValueError(
            "prefix-sharing reset (length > 0) requires an all-attention "
            "stack: recurrent per-slot state has no paged representation")
    layers = []
    for pos in range(cfg.period):
        tree = cache["layers"][pos]
        if cfg.layer_kind(pos) == "attn":
            layers.append(tree)
        else:
            layers.append(jax.tree.map(
                lambda v: v.at[:, slot].set(jnp.zeros_like(v[:, slot])),
                tree,
            ))
    return {"layers": layers,
            "len": cache["len"].at[slot].set(jnp.int32(length))}


def rollback_slot(cfg: ModelConfig, cache: dict, slot: int,
                  length: int) -> dict:
    """Truncate one slot's resident length to ``length`` — the device half
    of speculative-decoding rollback (DESIGN.md §11). Rejected drafted
    rows need no scrub: paged attention masks every position at and past
    ``len``, so truncating the length (plus returning the now-unreferenced
    tail pages host-side, ``PagePool.rollback``) makes them unobservable,
    exactly like the masked tail of a fresh page. Only valid for
    all-attention stacks: recurrent mixers advance per-slot state
    token-wise, and that state cannot be rewound by truncation — callers
    must refuse speculation there (``launch.spec.SpecDecoder`` raises at
    construction)."""
    if any(cfg.layer_kind(p) != "attn" for p in range(cfg.period)):
        raise ValueError(
            "rollback (length truncation) requires an all-attention "
            "stack: recurrent per-slot state cannot be rewound")
    if length < 0:
        raise ValueError(f"negative rollback length {length}")
    return {"layers": cache["layers"],
            "len": cache["len"].at[slot].set(jnp.int32(length))}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def apply_block(p, x, ctx: Ctx, pos: int, cache, ffn_gathered=None):
    """One block: mixer + (optional) FFN. Returns
    (x, new_cache, aux_loss, z_loss, stats) — ``stats`` is the MoE
    layer's obs.device telemetry pytree when
    ``ctx.pcfg.collect_router_stats`` is set and this block holds an MoE
    FFN, else None (dense / telemetry disabled)."""
    kind = ctx.cfg.layer_kind(pos)
    h = tfm.apply_norm(p["ln1"], x, ctx.cfg)
    if kind == "attn":
        out, new_cache = tfm.apply_attention(p["mixer"], h, ctx, pos, cache)
    elif kind == "mamba":
        out, new_cache = mamba.apply_mamba(p["mixer"], h, ctx, cache)
    elif kind == "mlstm":
        out, new_cache = xlstm.apply_mlstm(p["mixer"], h, ctx, cache)
    else:
        out, new_cache = xlstm.apply_slstm(p["mixer"], h, ctx, cache)
    if (ctx.decode_active is not None and ctx.mode == "decode"
            and kind != "attn"
            and cache is not None and new_cache is not None):
        # Continuous-batching macro-step: inactive slots freeze their
        # recurrent state (attention handles itself via the sink page /
        # masked rolling-buffer write).
        act = ctx.decode_active
        new_cache = jax.tree.map(
            lambda n, o: jnp.where(
                act.reshape((-1,) + (1,) * (n.ndim - 1)), n, o
            ),
            new_cache, cache,
        )
    x = x + out
    if "xattn" in p:
        x = x + tfm.apply_cross_attention(
            p["xattn"], tfm.apply_norm(p["ln_x"], x, ctx.cfg), ctx
        )
    aux = jnp.zeros((), jnp.float32)
    z = jnp.zeros((), jnp.float32)
    stats = None
    if "ffn" in p:
        h2 = tfm.apply_norm(p["ln2"], x, ctx.cfg)
        if ctx.cfg.is_moe_layer(pos):
            out = tfm.apply_moe_ffn(
                p["ffn"], h2, dataclasses.replace(ctx, layer_idx=pos),
                gathered=ffn_gathered,
            )
            if ctx.pcfg.collect_router_stats:
                y, aux, z, stats = out
            else:
                y, aux, z = out
        else:
            y = tfm.apply_dense_ffn(p["ffn"], h2, ctx)
        x = x + y
    x = constrain(x, (("dp",), "sp", None), ctx.pcfg, ctx.mesh)
    return x, new_cache, aux, z, stats


def _remat_policy(pcfg: ParallelConfig):
    cp = jax.checkpoint_policies
    if pcfg.cache_policy == "janus":
        return cp.save_only_these_names("gathered_w")
    if pcfg.cache_policy == "dots":
        return cp.checkpoint_dots
    return cp.nothing_saveable


#: Residency/hit accounting of the last pipeline-shared cache built by
#: run_layers (trace-time stats; populated on the first trace of a jitted
#: forward). Benchmarks and tests read it after a call.
LAST_PIPELINE_CACHE_STATS: Optional[dict] = None


def run_layers(layers, x, ctx: Ctx, cache_layers):
    cfg, pcfg = ctx.cfg, ctx.pcfg
    period = cfg.period
    # Router telemetry (DESIGN.md §12): when enabled the scan carry grows
    # a stats pytree summed over every MoE layer. Gated statically so the
    # default path's carry structure — and compiled HLO — is unchanged.
    collect = pcfg.collect_router_stats and cfg.moe is not None

    def period_fn(carry, xs):
        if collect:
            x, aux, z, stats = carry
        else:
            x, aux, z = carry
            stats = None
        lp, lc, gf = xs
        new_caches = []
        for pos in range(period):
            c_in = None if lc is None else lc[pos]
            g = None if gf is None else gf.get(pos)
            x, nc, a, zz, st = apply_block(lp[pos], x, ctx, pos, c_in,
                                           ffn_gathered=g)
            new_caches.append(nc)
            aux = aux + a
            z = z + zz
            if collect and st is not None:
                stats = obs_device.add_stats(stats, st)
        return ((x, aux, z, stats) if collect else (x, aux, z)), new_caches

    if pcfg.remat != "none" and ctx.mode == "train":
        period_fn = jax.checkpoint(
            period_fn, policy=_remat_policy(pcfg), prevent_cse=False
        )

    zero = jnp.zeros((), jnp.float32)
    if pcfg.scan_layers:
        if pcfg.cache_layers > 0 and cfg.moe is not None:
            raise ValueError(
                "cache_layers > 0 requires scan_layers=False (the "
                "pipeline-shared prefetch cache lives in the unrolled "
                "layer loop)"
            )
        if collect:
            init = (x, zero, zero, obs_device.zero_stats(
                cfg.moe.num_experts))
            (x, aux, z, stats), new_cache = jax.lax.scan(
                period_fn, init, (layers, cache_layers, None)
            )
        else:
            (x, aux, z), new_cache = jax.lax.scan(
                period_fn, (x, zero, zero), (layers, cache_layers, None)
            )
            stats = None
    else:
        n_periods = cfg.num_layers // period
        moe_positions = [
            pos for pos in range(period)
            if cfg.is_moe_layer(pos) and _ffn_kind(cfg, pos) == "moe"
        ]
        # Pipeline-shared cache (DESIGN.md §2): gather each period's MoE fsdp
        # weight factors OUTSIDE the island, holding at most cache_layers
        # gathered periods and prefetching period pp+1 before period pp's
        # compute ops are emitted (the all-gather overlaps the MXU). One
        # cache entry = ONE period (all its MoE positions together), so the
        # residency bound counts what is actually live even when a period
        # holds several MoE layers.
        #
        # Inference-side mechanism only: under the remat'd training step the
        # gathered trees would become jax.checkpoint inputs and be SAVED as
        # residuals for every period — Janus residency with a cache sticker
        # on it. There the remat policy (cache_policy="shared_cache",
        # backward re-gathers per layer) is the paper's cache; skip the
        # prefetcher.
        remat_train = pcfg.remat != "none" and ctx.mode == "train"
        pcache = None
        if (pcfg.cache_layers > 0 and moe_positions and pcfg.mode != "ep"
                and not remat_train):
            from repro.parallel.cache import (
                PipelineSharedCache,
                gather_ffn_params,
            )
            pcache = PipelineSharedCache(pcfg.cache_layers)

            # Overlap schedule (DESIGN.md §10): with overlap_dispatch, the
            # prefetcher gathers the data-centric layers' FULL expert
            # weights (fsdp AND tp factor) one period ahead, so the next
            # layer's expert collectives — not just its fsdp gather —
            # overlap the current layer's compute. The per-position level
            # is resolved ONCE with the island's own chooser
            # (moe_parallel._auto_layer_mode), so prefetcher and island can
            # never disagree: an "all"-gathered layer is exactly a layer
            # the island would have run data-centric, and the gathered
            # values equal the in-island gather's — bit-identical schedule.
            levels = {pos: "fsdp" for pos in moe_positions}
            if pcfg.overlap_dispatch and pcfg.mode == "auto":
                import types

                from repro.parallel.moe_parallel import (
                    MoEStatic,
                    _auto_layer_mode,
                )

                def _sds(v):
                    return (None if v is None
                            else jax.ShapeDtypeStruct(v.shape[1:], v.dtype))

                tokens = x.shape[0] * x.shape[1]
                for pos in moe_positions:
                    ffn = layers[pos]["ffn"]
                    stub = types.SimpleNamespace(
                        w_gate=_sds(ffn.get("w_gate")),
                        w1=_sds(ffn.get("w1")),
                    )
                    ms = MoEStatic(
                        num_experts=cfg.moe.num_experts,
                        top_k=cfg.moe.top_k,
                    )
                    mode_pos = _auto_layer_mode(
                        stub, ms, pcfg, ctx.mesh, tokens, pos
                    )
                    if mode_pos == "data_centric":
                        levels[pos] = "all"

            def gather_period(pp):
                out = {}
                for pos in moe_positions:
                    g = gather_ffn_params(
                        jax.tree.map(lambda v: v[pp], layers[pos]["ffn"]),
                        pcfg, ctx.mesh, collectives=levels[pos],
                    )
                    if levels[pos] == "all":
                        g["__collectives__"] = "all"
                    out[pos] = g
                return out

        carry = ((x, zero, zero, obs_device.zero_stats(cfg.moe.num_experts))
                 if collect else (x, zero, zero))
        outs = []
        for pp in range(n_periods):
            lp = jax.tree.map(lambda v: v[pp], layers)
            lc = (
                None
                if cache_layers is None
                else jax.tree.map(lambda v: v[pp], cache_layers)
            )
            gf = None
            if pcache is not None:
                gf = pcache.fetch(pp, lambda: gather_period(pp))
                if pcache.capacity_layers >= 2 and pp + 1 < n_periods:
                    # double-buffer: issue pp+1's gathers before pp computes
                    pcache.prefetch(pp + 1, lambda: gather_period(pp + 1))
            carry, nc = period_fn(carry, (lp, lc, gf))
            outs.append(nc)
        if collect:
            x, aux, z, stats = carry
        else:
            x, aux, z = carry
            stats = None
        if pcache is not None:
            global LAST_PIPELINE_CACHE_STATS
            LAST_PIPELINE_CACHE_STATS = pcache.stats()
        new_cache = (
            None
            if cache_layers is None
            else jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        )
    return x, aux, z, new_cache, stats


def _embed_in(params, inputs, cfg: ModelConfig, dtype):
    emb = params["embed"]
    if cfg.frontend == "siglip" and "patches" in inputs:
        patches = frontends.project_frontend(
            params["frontend"], inputs["patches"], dtype
        )
        x_txt = emb[inputs["tokens"]].astype(dtype)
        x = jnp.concatenate([patches, x_txt], axis=1)
    elif cfg.frontend == "encodec":
        x = frontends.project_frontend(
            params["frontend"], inputs["embeds"], dtype
        )
    else:
        x = emb[inputs["tokens"]].astype(dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    return x


def _logits_out(params, x, cfg: ModelConfig):
    # bf16 operands, f32 accumulation (MXU-native mixed precision).
    if cfg.num_codebooks > 1:
        return jnp.einsum(
            "bsd,cdv->bscv", x, params["cb_heads"],
            preferred_element_type=jnp.float32,
        )
    if cfg.tie_embeddings:
        return jnp.einsum(
            "bsd,vd->bsv", x, params["embed"],
            preferred_element_type=jnp.float32,
        )
    return jnp.einsum(
        "bsd,dv->bsv", x, params["head"],
        preferred_element_type=jnp.float32,
    )


def score_logits(params, hidden, cfg: ModelConfig):
    """Project final-norm hidden states at EVERY position to vocabulary
    logits ``(B, S, V)`` — the multi-position output head of the
    speculative verify step (DESIGN.md §11). ``forward(...,
    return_hidden=True)`` deliberately stops before the head so the
    prefill path can project a single row; verification needs all ``S``
    drafted rows, which is exactly the per-position amortization the
    paged chunk forward already paid for. f32 accumulation, same einsum
    as the single-row head."""
    if cfg.num_codebooks > 1:
        raise ValueError("score_logits does not support codebook heads")
    return _logits_out(params, hidden, cfg)


def forward(
    params: dict,
    inputs: Dict[str, jax.Array],
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    mesh: Optional[Mesh],
    *,
    mode: str,
    cache: Optional[dict] = None,
    x_spec: P = P(None, None, None),
    rng: Optional[jax.Array] = None,
    return_hidden: bool = False,
    paged: Optional[dict] = None,
    active: Optional[jax.Array] = None,
):
    """Returns (logits, new_cache, aux_loss, z_loss). With
    ``return_hidden`` the first element is the final normed hidden states
    instead (callers compute chunked logits/loss themselves). When
    ``pcfg.collect_router_stats`` is set a fifth element is appended: the
    obs.device stats pytree summed over every MoE layer (per-expert token
    counts, capacity drops, entropy/token sums; DESIGN.md §12).

    ``paged`` (decode only, DESIGN.md §7): ``{"table": (B, maxp) int32,
    "page_size": int}`` switches the KV write/read to the shared page pool
    of ``init_paged_cache``.

    ``active`` (decode only): (B,) bool continuous-batching mask. Inactive
    slots write nothing (paged: redirected to the sink page; dense: the
    rolling-buffer row is restored), freeze their recurrent state, and do
    not advance their length — the shape-stable macro-step both serving
    drivers batch around.
    """
    dtype = jnp.dtype(cfg.dtype)
    x = _embed_in(params, inputs, cfg, dtype)
    b, s, _ = x.shape

    if mode == "decode":
        cache_len = cache["len"]
        positions = cache_len[:, None]
    elif mode == "prefill" and paged is not None:
        # chunk-extension prefill: this chunk continues from the tokens
        # already resident in the slot's pages
        cache_len = cache["len"]
        positions = cache_len[:, None] + jnp.arange(s)[None]
    else:
        cache_len = None
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if (paged is not None or active is not None) and mode not in (
            "decode", "prefill"):
        raise ValueError("paged cache / active mask are serving-side only")

    ctx = Ctx(
        cfg=cfg,
        pcfg=pcfg,
        mesh=mesh,
        mode=mode,
        positions=positions,
        cache_len=cache_len,
        x_spec=x_spec,
        rng=rng,
        cond=inputs.get("cond"),
        paged=paged,
        decode_active=active,
    )
    x = constrain(x, (("dp",), "sp", None), pcfg, mesh)
    cache_layers = None if cache is None else cache["layers"]
    x, aux, z, new_cache_layers, stats = run_layers(
        params["layers"], x, ctx, cache_layers
    )
    x = tfm.apply_norm(params["final_norm"], x, cfg)

    if return_hidden:
        logits = x
    elif mode == "prefill":
        logits = _logits_out(params, x[:, -1:], cfg)
    else:
        logits = _logits_out(params, x, cfg)

    new_cache = None
    if cache is not None:
        if mode == "decode" and active is not None:
            new_len = cache["len"] + active.astype(jnp.int32)
        elif mode == "decode":
            new_len = cache["len"] + s
        elif mode == "prefill" and paged is not None:
            adv = (jnp.full((b,), s, jnp.int32) if active is None
                   else active.astype(jnp.int32).sum(axis=1))
            new_len = cache["len"] + adv
        else:
            new_len = jnp.full((b,), s, jnp.int32)
        new_cache = {"layers": new_cache_layers, "len": new_len}
    n_moe = max(sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers)), 1)
    if pcfg.collect_router_stats:
        if stats is None:
            stats = obs_device.zero_stats(
                cfg.moe.num_experts if cfg.moe is not None else 1)
        return logits, new_cache, aux / n_moe, z / n_moe, stats
    return logits, new_cache, aux / n_moe, z / n_moe
