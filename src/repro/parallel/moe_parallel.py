"""Distributed MoE layer execution — the paper's §4.3 on a TPU mesh.

One shard_map island per MoE layer gives exact control of the collective
schedule (auditable in the compiled HLO):

  model-centric (paper TP): expert hidden dim sharded over "model"; tokens
    all-gathered over "model"; partial outputs reduced. NO weight movement.
  data-centric (paper Janus-style): expert params sharded over every mesh
    axis; all-gathered to each device at use; tokens never move. The
    pipeline-shared cache (bounded gathered-param residency) is realised two
    ways: the surrounding remat policy (gathered params are not saved as
    backward residuals, the backward re-gathers layer by layer) and, in the
    unrolled layer loop, parallel.cache.PipelineSharedCache's double-buffered
    prefetch (DESIGN.md §2).
  hybrid (beyond paper): fsdp gather over ("pod","data") + TP over "model".
  auto (paper §4.5 / Fig. 10, runtime form): hybrid physical layout; each
    MoE layer picks data- or model-centric dispatch at trace time via
    parallel.autotune's roofline — "move tokens over TP" vs "gather the
    weights' TP factor" is a per-layer ``layer_mode`` choice inside the
    island, so prefill and decode land on opposite sides of the crossover.
  ep (baseline): classic expert parallelism with all-to-all + capacity
    buffer — exists to quantify the paper's motivation in the roofline.

Collective schedule options (DESIGN.md §2):
  "ag_ar" — paper-faithful: tokens replicated over TP, outputs all-reduced.
  "ag_rs" — bandwidth-optimal sequence-parallel form: all-gather tokens in,
            reduce-scatter outputs; 2x less collective traffic at scale.

Everything here is a *token-level* API: x is (N_local, D) inside the island.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import baselines, espec
from repro.core.reindex import build_reindex
from repro.core.routing import route
from repro.obs import device as obs_device
from repro.parallel.sharding import ParallelConfig


class MoEParams(NamedTuple):
    """Expert parameter shards as seen inside the island (local views).

    The ``*_scale`` leaves are present only for true-quantized expert
    weights (int8/fp8 payloads from ``quant.core.quantize_ffn``,
    DESIGN.md §8): block-wise per-(expert, tile) scales the fused-dequant
    kernels consume. Routers and biases are never quantized."""
    router: jax.Array                  # (D, E) replicated
    w_gate: Optional[jax.Array] = None  # (E, D_l, F_l) glu
    w_up: Optional[jax.Array] = None    # (E, D_l, F_l) glu
    w_down: Optional[jax.Array] = None  # (E, F_l, D_l) glu
    w1: Optional[jax.Array] = None      # (E, D_l, F_l) mlp
    b1: Optional[jax.Array] = None      # (E, F_l) mlp
    w2: Optional[jax.Array] = None      # (E, F_l, D_l) mlp
    b2: Optional[jax.Array] = None      # (E, D_l) mlp
    w_gate_scale: Optional[jax.Array] = None  # (E, nD, nF)
    w_up_scale: Optional[jax.Array] = None
    w_down_scale: Optional[jax.Array] = None  # (E, nF, nD)
    w1_scale: Optional[jax.Array] = None
    w2_scale: Optional[jax.Array] = None


class MoEStatic(NamedTuple):
    """Static (trace-time) MoE layer hyperparameters shared by every
    island implementation (paper §4.2 routing + §4.3 execution)."""
    num_experts: int
    top_k: int
    act: str = "silu"
    glu: bool = True
    norm_topk: bool = True
    softmax_after_topk: bool = False


def _shard_map(body, mesh, in_specs, out_specs):
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _ag(x, axes, dim):
    """all_gather over possibly-multiple mesh axes (tiled)."""
    if not axes:
        return x
    return lax.all_gather(x, axes, axis=dim, tiled=True)


def _ag_hier(x, axes, dim, hier: bool):
    """Tiled all-gather, optionally phased per interconnect level.

    Hierarchical (DESIGN.md §10): gather the minor (intra-node, fast) axis
    first, then the major (inter-node, slow) one — after the intra phase the
    cross-node exchange moves each node's already-assembled shard once. The
    phased gather concatenates in exactly the tuple axis order, so its
    result is bitwise-identical to the single flat gather; only the
    collective decomposition (and therefore the per-link traffic) differs."""
    if not axes:
        return x
    if hier and isinstance(axes, tuple) and len(axes) > 1:
        for ax in reversed(axes):
            x = lax.all_gather(x, ax, axis=dim, tiled=True)
        return x
    return lax.all_gather(x, axes, axis=dim, tiled=True)


def _axis_size(mesh, axes) -> int:
    """Extent of a (possibly tuple) mesh axis group."""
    if not axes:
        return 1
    size = 1
    for ax in (axes if isinstance(axes, tuple) else (axes,)):
        size *= mesh.shape[ax]
    return int(size)


def _hier_schedule(cfg: ParallelConfig, mesh, tp) -> bool:
    """True when the island should run the two-level collective schedule:
    a topology is attached AND the TP group spans a real "node" axis
    (DESIGN.md §10). Everything else — no topology, no node axis, or a
    single-node mesh — short-circuits to the flat single-level collectives,
    so those configs compile to HLO bitwise-identical to the pre-topology
    path."""
    return (
        getattr(cfg, "topology", None) is not None
        and isinstance(tp, tuple)
        and len(tp) == 2
        and int(mesh.shape[tp[0]]) > 1
    )


def _psum_hier(y, axes, hier: bool):
    """All-reduce, phased per level when hierarchical: the intra-node psum
    (node-local combine) runs first so only node-combined partial sums cross
    the slow inter-node fabric (DESIGN.md §10)."""
    if hier and isinstance(axes, tuple) and len(axes) > 1:
        for ax in reversed(axes):
            y = lax.psum(y, ax)
        return y
    return lax.psum(y, axes)


def _mask_rank0(b, tp_axis):
    """Keep a partial-sum bias on TP rank 0 only (avoids k_tp-fold bias)."""
    if b is None or tp_axis is None:
        return b
    rank = lax.axis_index(tp_axis)
    return jnp.where(rank == 0, b, jnp.zeros_like(b))


def hexa_moe_island(
    x: jax.Array,
    p: MoEParams,
    ms: MoEStatic,
    cfg: ParallelConfig,
    mesh: Mesh,
    *,
    tokens_sharded_tp: bool,
    noise_rng: Optional[jax.Array] = None,
    layer_mode: Optional[str] = None,
    pregathered=False,
    token_valid: Optional[jax.Array] = None,
):
    """Body of the shard_map island: local tokens x (N_l, D) -> (y, aux, z).

    ``tokens_sharded_tp``: whether the incoming token dim is sharded over the
    TP axis (training/prefill with SP) or replicated (decode).
    ``layer_mode``: per-layer dispatch under ``cfg.mode == "auto"`` —
    "data_centric" gathers the weights' TP factor and keeps tokens (and the
    output) local; "model_centric"/None keeps the TP compute split and moves
    tokens. ``pregathered``: which weight collectives already ran outside
    the island (pipeline-shared cache): False = none, True/"fsdp" = the
    fsdp factor, "all" = fsdp AND the data-centric tp factor (the overlap
    schedule, DESIGN.md §10) — skip the corresponding in-island gathers.
    ``token_valid``: optional (N_l,) bool — heterogeneous-plan (Eq. 1) tail
    mask (DESIGN.md §6): invalid rows route with gate 0, produce exactly-zero
    output rows and exactly-zero weight gradients, and are excluded from the
    aux losses. Travels through the same TP gather as the tokens.

    With ``cfg.topology`` on a two-level ("node", "model") mesh the
    collectives run the hierarchical schedule (DESIGN.md §10): token and
    weight gathers are phased intra-node -> inter-node (bitwise-identical
    values), and the output combine reduces node-locally BEFORE the
    cross-node exchange, shrinking inter-node partial-sum traffic by the
    node size. Flat/uniform meshes short-circuit to the single-level path.
    """
    axes = cfg.axes(mesh)
    fsdp, tp = axes["fsdp"], axes["tp"]
    if pregathered:
        fsdp = ()
    dc = layer_mode == "data_centric" and tp is not None
    gather_tokens = tp is not None and tokens_sharded_tp and not dc
    hier = _hier_schedule(cfg, mesh, tp)

    if gather_tokens:
        x = _ag_hier(x, tp, 0, hier)
        if token_valid is not None:
            token_valid = _ag_hier(token_valid, tp, 0, hier)

    with jax.named_scope("moe.route"):
        r = route(
            x, p.router, ms.top_k,
            norm_topk=ms.norm_topk,
            softmax_after_topk=ms.softmax_after_topk,
            noise_rng=noise_rng,
            valid_mask=token_valid,
        )
        # Router telemetry (DESIGN.md §12): device-side accumulators over
        # the rows this device routed; the caller de-duplicates
        # TP-replicated counts before they leave the shard_map.
        stats = (obs_device.expert_stats(r.expert_idx, r.probs,
                                         ms.num_experts,
                                         valid_mask=token_valid)
                 if cfg.collect_router_stats else None)
        ri = build_reindex(r.expert_idx, r.gates, ms.num_experts, cfg.blk)

    # True-quantized expert weights (int8/fp8 payloads + block scales,
    # DESIGN.md §8): the scales are NOT sharded congruently with a
    # sliced weight, so the path requires whole expert weights per device
    # (serving without TP over experts, or the per-device hetero_exec
    # programs). QAT (cfg.quant) fake-quants the gathered weights instead
    # and composes with any sharding.
    quantized = p.w_gate_scale is not None or p.w1_scale is not None
    if quantized and (fsdp or tp is not None):
        raise NotImplementedError(
            "true-quantized expert weights require ungathered whole-expert "
            "layouts (no fsdp/tp over expert weights); use cfg.quant (QAT "
            "fake-quant) on sharded meshes"
        )

    def maybe_fq(w):
        if w is None or quantized or cfg.quant == "none":
            return w
        from repro.quant.core import fake_quant
        return fake_quant(w, cfg.quant, cfg.quant_tile)

    # data-centric: gather the weights' TP factor (unless the overlap
    # schedule already gathered it outside the island, pregathered="all").
    tp_w = tp if dc and pregathered != "all" else None
    name = checkpoint_name  # pipeline-shared cache tagging

    def ag_w(w, dim):
        return _ag_hier(w, tp_w, dim, hier)

    if ms.glu:
        wg = name(maybe_fq(ag_w(_ag(p.w_gate, fsdp, 1), 2)), "gathered_w")
        wu = name(maybe_fq(ag_w(_ag(p.w_up, fsdp, 1), 2)), "gathered_w")
        wd = name(maybe_fq(ag_w(_ag(p.w_down, fsdp, 2), 1)), "gathered_w")
        scales = ((p.w_gate_scale, p.w_up_scale, p.w_down_scale)
                  if quantized else None)
        y = espec.moe_glu(
            x, ri, wg, wu, wd, scales=scales, act=ms.act, impl=cfg.impl,
            fused=cfg.fused_ffn,
        )
    else:
        w1 = name(maybe_fq(ag_w(_ag(p.w1, fsdp, 1), 2)), "gathered_w")
        w2 = name(maybe_fq(ag_w(_ag(p.w2, fsdp, 2), 1)), "gathered_w")
        # (E, F_l) bias: local TP slice adds locally; dc gathers it full.
        b1 = ag_w(p.b1, 1)
        b2 = _ag(p.b2, fsdp, 1)
        if not dc:
            b2 = _mask_rank0(b2, tp)
        scales = (p.w1_scale, p.w2_scale) if quantized else None
        y = espec.moe_mlp(
            x, ri, w1, b1, w2, b2, scales=scales, act=ms.act, impl=cfg.impl,
            fused=cfg.fused_ffn,
        )

    if tp is not None and not dc:
        # Partial products over the TP-sharded contraction dim.
        if gather_tokens and cfg.collective_schedule == "ag_rs":
            if hier:
                # Node-local combine BEFORE the cross-node exchange
                # (DESIGN.md §10): the intra-node reduce collapses node_size
                # partial sums into one, so only the combined rows cross the
                # slow fabric; the final slice keeps this rank's chunk of
                # its node's scatter share — same row ownership as the flat
                # reduce-scatter over the ("node", "model") tuple.
                node_ax, model_ax = tp
                y = lax.psum(y, model_ax)
                y = lax.psum_scatter(
                    y, node_ax, scatter_dimension=0, tiled=True)
                nl = y.shape[0] // mesh.shape[model_ax]
                y = lax.dynamic_slice_in_dim(
                    y, lax.axis_index(model_ax) * nl, nl, 0)
            else:
                y = lax.psum_scatter(y, tp, scatter_dimension=0, tiled=True)
        elif gather_tokens:
            # Paper-faithful ag_ar: all-reduce, then keep own token chunk.
            y = _psum_hier(y, tp, hier)
            nl = y.shape[0] // _axis_size(mesh, tp)
            y = lax.dynamic_slice_in_dim(y, lax.axis_index(tp) * nl, nl, 0)
        else:
            y = _psum_hier(y, tp, hier)

    # Per-device aux losses; mean over the data axes happens in the caller
    # after the island returns (values are replicated within TP).
    if stats is not None:
        return y, r.aux_loss, r.z_loss, stats
    return y, r.aux_loss, r.z_loss


def ep_moe_island(
    x: jax.Array,
    p: MoEParams,
    ms: MoEStatic,
    cfg: ParallelConfig,
    mesh: Mesh,
    *,
    tokens_sharded_tp: bool,
    noise_rng: Optional[jax.Array] = None,
    token_valid: Optional[jax.Array] = None,
):
    """Expert-parallel baseline: experts sharded over "model", tokens travel
    by all-to-all with a capacity buffer (padding + drops) — the classic
    GShard/Tutel execution the paper replaces.

    ``token_valid``: heterogeneous-plan (Eq. 1, DESIGN.md §6) tail mask.
    Masked rows get gate 0 so their combine output and weight gradients are
    exactly zero; they may still occupy capacity slots (the EP baseline's
    capacity buffer is exactly the redundancy the paper removes, so the
    masked path is not optimised further here)."""
    if p.w_gate_scale is not None or p.w1_scale is not None:
        raise NotImplementedError(
            "the EP baseline does not support quantized expert weights"
        )
    tp = cfg.axes(mesh)["tp"]
    if isinstance(tp, tuple):
        raise NotImplementedError(
            "the EP baseline does not support two-level (node) meshes; use "
            "the hexa modes for hierarchical dispatch (DESIGN.md §10)"
        )
    ep = mesh.shape[tp] if tp else 1
    e, k = ms.num_experts, ms.top_k
    assert e % max(ep, 1) == 0, "EP baseline needs num_experts % ep == 0"

    with jax.named_scope("moe.route"):
        r = route(
            x, p.router, k,
            norm_topk=ms.norm_topk,
            softmax_after_topk=ms.softmax_after_topk,
            noise_rng=noise_rng,
            valid_mask=token_valid,
        )
    n, d = x.shape
    capacity = max(int((n * k / e) * cfg.capacity_factor), 1)

    rank, _ = baselines._dispatch_ranks(r.expert_idx, e)
    keep = rank < capacity
    stats = None
    if cfg.collect_router_stats:
        # Capacity-overflow drops (DESIGN.md §12): valid token slots whose
        # dispatch rank exceeded the buffer — the redundancy the paper's
        # modes remove, now measurable against them.
        vt = (jnp.ones((n,), jnp.int32) if token_valid is None
              else token_valid.astype(jnp.int32))
        dropped = jnp.sum((~keep).astype(jnp.int32) * vt[:, None])
        stats = obs_device.expert_stats(
            r.expert_idx, r.probs, e, valid_mask=token_valid,
            dropped=dropped)
    slot = r.expert_idx * capacity + rank
    slot = jnp.where(keep, slot, e * capacity)
    buf = jnp.zeros((e * capacity, d), x.dtype)
    src = jnp.broadcast_to(x[:, None, :], (n, k, d)).reshape(n * k, d)
    buf = buf.at[slot.reshape(-1)].set(src, mode="drop").reshape(e, capacity, d)

    if tp is not None and ep > 1:
        # (E, C, D) -> exchange expert groups: device m ends up with its
        # E/ep experts' tokens from every peer. all_to_all with
        # split=concat=0 is an involution, so the return path mirrors it.
        buf = buf.reshape(ep, e // ep, capacity, d)
        buf = lax.all_to_all(buf, tp, split_axis=0, concat_axis=0)
        # (src=ep, my_experts, C, D) -> expert-major rows
        buf = buf.transpose(1, 0, 2, 3).reshape(e // ep, ep * capacity, d)

    wg, wu, wd = p.w_gate, p.w_up, p.w_down  # local (E/ep, D, F) dense
    if ms.glu:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(buf.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu.astype(buf.dtype))
        out = jnp.einsum("ecf,efd->ecd", h, wd.astype(buf.dtype))
    else:
        h = espec.ACTIVATIONS[ms.act](
            jnp.einsum("ecd,edf->ecf", buf, p.w1.astype(buf.dtype))
            + (p.b1[:, None].astype(buf.dtype) if p.b1 is not None else 0)
        )
        out = jnp.einsum("ecf,efd->ecd", h, p.w2.astype(buf.dtype))
        if p.b2 is not None:
            out = out + p.b2[:, None].astype(buf.dtype)

    if tp is not None and ep > 1:
        out = out.reshape(e // ep, ep, capacity, d).transpose(1, 0, 2, 3)
        out = lax.all_to_all(out, tp, split_axis=0, concat_axis=0)
        out = out.reshape(e, capacity, d)

    y_flat = out.reshape(e * capacity, d)
    got = y_flat[jnp.minimum(slot, e * capacity - 1).reshape(-1)].reshape(n, k, d)
    gates = (r.gates * keep.astype(r.gates.dtype))[..., None].astype(x.dtype)
    y = jnp.sum(got * gates, axis=1)
    if stats is not None:
        return y, r.aux_loss, r.z_loss, stats
    return y, r.aux_loss, r.z_loss


def _auto_layer_mode(
    p: MoEParams,
    ms: MoEStatic,
    cfg: ParallelConfig,
    mesh: Optional[Mesh],
    tokens: int,
    layer_idx: Optional[int],
) -> str:
    """Resolve the per-layer dispatch for cfg.mode == "auto" from static
    shapes (paper Fig. 10 roofline; see parallel.autotune)."""
    from repro.parallel import autotune

    w = p.w_gate if p.w_gate is not None else p.w1
    e, d, f = w.shape
    if mesh is not None and getattr(mesh, "axis_names", ()):
        dp_axes = cfg.axes(mesh)["dp"]
        dp = 1
        for a in dp_axes:
            dp *= mesh.shape[a]
        tokens = max(tokens // max(dp, 1), 1)  # workload per TP group
    return autotune.resolve_layer_mode(
        tokens, d=d, f=f, e=ms.num_experts, k=ms.top_k,
        cfg=cfg, mesh=mesh, layer_idx=layer_idx,
    )


def _hetero_mask_counts(plan, x_spec: P, mesh: Optional[Mesh], b: int):
    """Static resolution of the Eq. 1 token mask (DESIGN.md §6).

    Returns ``(token_counts, batch_axes)`` when the plan's data split is
    uneven at this sharding — the island then builds the per-device validity
    mask — or ``None`` when no masking is needed: no plan, no mesh, or a
    uniform split that exactly fills every shard (the short-circuit that
    keeps the uniform path's HLO bitwise unchanged)."""
    if plan is None or getattr(plan, "token_counts", None) is None:
        return None
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return None
    entry = x_spec[0]
    baxes = (() if entry is None
             else entry if isinstance(entry, tuple) else (entry,))
    extent = 1
    for a in baxes:
        extent *= mesh.shape[a]
    counts = tuple(int(c) for c in plan.token_counts)
    if len(counts) != extent:
        raise ValueError(
            f"hetero_plan.token_counts has {len(counts)} entries but the "
            f"batch dim is sharded over {extent} devices"
        )
    local_b = b // extent
    if max(counts) > local_b:
        raise ValueError(
            f"hetero_plan assigns {max(counts)} batch rows to a device but "
            f"the padded shard holds only {local_b} (global batch {b})"
        )
    if all(c == local_b for c in counts):
        return None  # uniform plan: no masking, identical trace
    return counts, baxes


def moe_layer(
    x: jax.Array,                    # (B, S, D) global
    p: MoEParams,                    # sharded per resolve_spec
    ms: MoEStatic,
    cfg: ParallelConfig,
    mesh: Optional[Mesh],
    *,
    x_spec: P,                       # how (B, S, D) is sharded
    noise_rng: Optional[jax.Array] = None,
    layer_idx: Optional[int] = None,
    pregathered=False,
):
    """Distributed MoE FFN over a (B, S, D) activation. Returns
    (y, aux_loss, z_loss) with y sharded like x — or, when
    ``cfg.collect_router_stats`` is set, (y, aux_loss, z_loss, stats)
    where ``stats`` is the replicated obs.device telemetry pytree
    (globally-exact per-expert token counts; DESIGN.md §12).

    ``layer_idx`` feeds the auto-mode plan lookup; ``pregathered`` marks
    which weight collectives already ran outside (pipeline-shared cache
    path): True/"fsdp" = the fsdp factor, "all" = fsdp AND the tp factor
    (the overlap schedule, DESIGN.md §10 — the layer then necessarily runs
    data-centric dispatch, which is what the overlap prefetcher resolved).

    ``cfg.hetero_plan`` (DESIGN.md §6): when the plan's Eq. 1 ``token_counts``
    are uneven, each batch-group member masks its shard's tail batch rows
    inside the island (the SPMD shapes stay uniform). A uniform plan
    short-circuits entirely — same trace as no plan."""
    b, s, d = x.shape

    island = ep_moe_island if cfg.mode == "ep" else hexa_moe_island
    layer_mode = None
    if island is hexa_moe_island:
        if pregathered == "all":
            # The overlap prefetcher already gathered the weights' tp
            # factor for this layer — it necessarily runs data-centric.
            layer_mode = "data_centric"
        elif cfg.mode == "auto":
            layer_mode = _auto_layer_mode(p, ms, cfg, mesh, b * s, layer_idx)
        island = functools.partial(
            island, layer_mode=layer_mode, pregathered=pregathered
        )

    mask_counts = _hetero_mask_counts(cfg.hetero_plan, x_spec, mesh, b)

    collect = cfg.collect_router_stats

    if mesh is None:
        # Single-process path (unit tests): plain local computation.
        local_cfg = cfg
        xf = x.reshape(b * s, d)
        out = island(
            xf, p, ms, local_cfg, _SINGLE_MESH, tokens_sharded_tp=False,
            noise_rng=noise_rng,
        )
        if collect:
            y, aux, z, stats = out
            return y.reshape(b, s, d), aux, z, stats
        y, aux, z = out
        return y.reshape(b, s, d), aux, z

    tokens_tp = x_spec[1] is not None  # seq dim sharded over "model"?

    # Telemetry de-duplication factor (DESIGN.md §12): when tokens are
    # gathered over TP (model-centric training/prefill) or replicated over
    # TP (decode), every TP rank routes — and counts — the same tokens, so
    # the psum'd totals are exact multiples of the true counts. Static per
    # layer, so the integer floor-division below is exact.
    stat_dup = 1
    if collect:
        tp = cfg.axes(mesh)["tp"]
        if tp is not None:
            tp_size = 1
            for a in (tp if isinstance(tp, tuple) else (tp,)):
                tp_size *= int(mesh.shape[a])
            if cfg.mode == "ep":
                stat_dup = 1 if tokens_tp else tp_size
            else:
                dc = layer_mode == "data_centric"
                stat_dup = 1 if (tokens_tp and dc) else tp_size

    def body(xl, pl, rngl):
        bl, sl, _ = xl.shape
        tv = None
        bv = None
        if mask_counts is not None:
            counts, baxes = mask_counts
            # This device's position in the batch-sharding group, then its
            # Eq. 1 share: row r of the flat (bl*sl) shard belongs to batch
            # element r // sl; elements past the share are masked tail.
            rank = jnp.zeros((), jnp.int32)
            for a in baxes:
                rank = rank * mesh.shape[a] + lax.axis_index(a)
            bv = jnp.asarray(counts, jnp.int32)[rank]
            tv = (jnp.arange(bl * sl, dtype=jnp.int32) // sl) < bv
        out = island(
            xl.reshape(bl * sl, d), pl, ms, cfg, mesh,
            tokens_sharded_tp=tokens_tp,
            noise_rng=None if rngl is None else rngl[0],
            token_valid=tv,
        )
        if collect:
            y, aux, z, stats = out
        else:
            (y, aux, z), stats = out, None
        if bv is None:
            # Mean aux over all devices (aux is per-local-batch).
            aux = lax.pmean(aux, mesh.axis_names)
            z = lax.pmean(z, mesh.axis_names)
        else:
            # Uneven plan: each device's aux is a mean over ITS valid rows,
            # so average them weighted by valid-token count — the result is
            # the masked mean over all valid tokens, independent of how a
            # replan shuffles the shares (DESIGN.md §6).
            w = (bv * sl).astype(jnp.float32)
            wsum = lax.psum(w, mesh.axis_names)
            aux = lax.psum(aux * w, mesh.axis_names) / wsum
            z = lax.psum(z * w, mesh.axis_names) / wsum
        if collect:
            # Global totals: sum every device's local counts, then divide
            # out the TP replication factor (exact — see stat_dup above).
            stats = {k: lax.psum(v, mesh.axis_names)
                     for k, v in stats.items()}
            if stat_dup > 1:
                stats = {
                    "expert_tokens": stats["expert_tokens"] // stat_dup,
                    "dropped_tokens": stats["dropped_tokens"] // stat_dup,
                    "entropy_sum": stats["entropy_sum"] / stat_dup,
                    "tokens": stats["tokens"] // stat_dup,
                }
            return y.reshape(bl, sl, d), aux, z, stats
        return y.reshape(bl, sl, d), aux, z

    p_specs = _param_specs(p, ms, cfg, mesh, pregathered=pregathered)
    rng_arg = None if noise_rng is None else noise_rng[None]
    rng_spec = None if noise_rng is None else P()
    if collect:
        stat_specs = {k: P() for k in obs_device.STAT_KEYS}
        y, aux, z, stats = _shard_map(
            body,
            mesh,
            in_specs=(x_spec, p_specs, rng_spec),
            out_specs=(x_spec, P(), P(), stat_specs),
        )(x, p, rng_arg)
        return y, aux, z, stats
    y, aux, z = _shard_map(
        body,
        mesh,
        in_specs=(x_spec, p_specs, rng_spec),
        out_specs=(x_spec, P(), P()),
    )(x, p, rng_arg)
    return y, aux, z


def _param_specs(p: MoEParams, ms: MoEStatic, cfg: ParallelConfig, mesh: Mesh,
                 *, pregathered=False):
    """Physical specs for MoEParams matching parallel.sharding's resolution.

    ``pregathered``: weight leaves arrive with their fsdp factor already
    gathered (parallel.cache.gather_ffn_params) — drop "fsdp" from their
    logical specs before resolving; ``"all"`` (the overlap schedule,
    DESIGN.md §10) additionally drops "tp" (the expert collectives were
    prefetched too). Logical specs come from the same MOE_PARAM_LOGICAL /
    EP_PARAM_LOGICAL tables the init/gather paths use, so the three can
    never drift apart."""
    from repro.parallel.cache import _drop_axes
    from repro.parallel.sharding import divisible_spec, resolve_spec

    table = EP_PARAM_LOGICAL if cfg.mode == "ep" else MOE_PARAM_LOGICAL
    drop = ()
    if pregathered:
        drop = ("fsdp", "tp") if pregathered == "all" else ("fsdp",)

    def spec_of(name):
        v = getattr(p, name)
        if v is None:
            return None
        logical = table[name]
        if drop and name != "router":
            logical = _drop_axes(logical, drop)
        phys = resolve_spec(logical, cfg, mesh)
        return divisible_spec(v.shape, phys, mesh)

    return MoEParams(**{name: spec_of(name) for name in MoEParams._fields})


#: Block-wise scale leaves of quantized expert weights stay replicated —
#: their (E, n1, n2) blocks do not tile congruently under arbitrary
#: weight sharding, and the quantized path requires whole-expert layouts
#: anyway (see hexa_moe_island's guard).
_SCALE_LOGICAL = {
    "w_gate_scale": (None, None, None),
    "w_up_scale": (None, None, None),
    "w_down_scale": (None, None, None),
    "w1_scale": (None, None, None),
    "w2_scale": (None, None, None),
}

MOE_PARAM_LOGICAL = {
    "router": (None, None),
    "w_gate": (None, "fsdp", "tp"),
    "w_up": (None, "fsdp", "tp"),
    "w_down": (None, "tp", "fsdp"),
    "w1": (None, "fsdp", "tp"),
    "b1": (None, "tp"),
    "w2": (None, "tp", "fsdp"),
    "b2": (None, "fsdp"),
    **_SCALE_LOGICAL,
}

EP_PARAM_LOGICAL = {
    "router": (None, None),
    "w_gate": ("tp", None, None),
    "w_up": ("tp", None, None),
    "w_down": ("tp", None, None),
    "w1": ("tp", None, None),
    "b1": ("tp", None),
    "w2": ("tp", None, None),
    "b2": ("tp", None),
    **_SCALE_LOGICAL,
}


class _FakeMesh:
    """Stands in for a mesh in the single-process path."""
    axis_names = ()
    shape = {}


_SINGLE_MESH = _FakeMesh()
