"""Sharding substrate: logical axes, parameter trees, parallel config.

Logical axis names used by every model definition:

  "fsdp"  — ZeRO-3 style parameter sharding, gathered at use. Maps to the
            ("pod", "data") mesh axes (the paper's *data-centric* gathering).
  "tp"    — tensor parallelism, kept sharded through compute. Maps to
            "model" (the paper's *model-centric* hidden-dim split).
  "dp"    — batch data parallelism: ("pod", "data").
  "sp"    — sequence parallelism for activations: "model".

The paper's two configurations are corners of this family (DESIGN.md §3):
model-centric disables "fsdp" (params replicated over data, TP compute);
data-centric folds "tp" into the gather (params fully gathered at use, no
TP compute). ``ParallelConfig.mode`` selects the mapping; mode="auto" keeps
the hybrid layout and lets each MoE layer pick its dispatch at trace time
from the parallel.autotune roofline (paper §4.5 / Fig. 10).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class Param:
    """A parameter leaf: value (or ShapeDtypeStruct) + logical spec.

    Registered as a pytree node with ``spec`` as static aux data, so Param
    trees pass through jax.eval_shape (abstract init).
    """
    __slots__ = ("value", "spec")

    def __init__(self, value: Any, spec: tuple):
        self.value = value
        self.spec = tuple(spec)

    def __repr__(self):
        return f"Param({self.value!r}, spec={self.spec})"


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), p.spec),
    lambda spec, children: Param(children[0], spec),
)


def is_param(x) -> bool:
    """True for Param leaves (the is_leaf predicate for Param trees)."""
    return isinstance(x, Param)


def split_tree(tree):
    """Split a tree of Param into (values, logical_specs)."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    specs = jax.tree.map(lambda p: p.spec, tree, is_leaf=is_param)
    return values, specs


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """How logical axes map onto the physical mesh.

    mode:
      "hybrid"        — fsdp -> (pod, data), tp -> model  (default at scale)
      "model_centric" — fsdp -> (),          tp -> model  (paper §4.3 TP)
      "data_centric"  — fsdp -> ALL axes,    tp -> ()     (paper §4.3 gather)
      "auto"          — hybrid physical layout; each MoE layer picks its
                        data-/model-centric collective schedule at trace time
                        from the roofline (parallel.autotune, paper Fig. 10)
      "ep"            — expert parallelism baseline (all-to-all)
    collective_schedule:
      "ag_ar" — paper-faithful: tokens replicated in TP, outputs all-reduced.
      "ag_rs" — bandwidth-optimal: all-gather in, reduce-scatter out (SP).
    cache_policy:
      "shared_cache" — paper's pipeline-shared cache: gathered params are NOT
                       saved for backward (remat re-gathers per layer).
      "janus"        — retain gathered params for backward (memory baseline).
      "none"         — no remat at all.
    fused_ffn:
      None (default) — fused forward expert FFN (kernels.ops.esffn_*,
      DESIGN.md §5) follows the impl default: ON for the TPU "pallas"
      path, OFF for the XLA impls. True/False force it either way.
    Auto-mode knobs (ignored for other modes):
      forced_layer_mode — pin every MoE layer's dispatch ("data_centric" /
                          "model_centric"); bypasses the chooser entirely.
      layer_mode_plan   — per-period-position plan from
                          autotune.plan_layer_modes (None entries defer to
                          the chooser).
      device_latencies  — heterogeneous proxy latencies (core.hetero t_i);
                          shrink the chooser's effective TP group size.
    Quantization (DESIGN.md §8):
      quant      — "none" | "int8" | "fp8": QAT fake-quant of the expert
                   weights inside the MoE islands (quant.core.fake_quant,
                   straight-through grads; routers/dense layers untouched).
                   Ignored when the params already carry true int8/fp8
                   payloads + '<name>_scale' leaves (serving-side
                   quant.core.quantize_lm_params) — those dispatch the
                   fused-dequant kernels directly.
      quant_tile — block size of the per-(expert, tile) scales.
    Pipeline-shared cache realisation (models.lm unrolled layer loop):
      cache_layers — gathered-period residency bound for the prefetching
                     cache (one entry = one period's MoE layers; 2 = double
                     buffer); 0 disables it. Requires scan_layers=False.
                     Inference-side: the remat'd train step skips it (the
                     remat policy is training's cache) so gathered trees
                     never become checkpoint residuals.
    Heterogeneous execution (paper §4.4 Eq. 1/2, DESIGN.md §6):
      hetero_plan — a ``core.hetero.HeteroPlan``. Its ``token_counts``
                    (Eq. 1) make the MoE islands mask each data-group
                    member's tail rows (the SPMD shard stays a uniform
                    padded shape; rows past the device's share contribute
                    zero output, zero gradient, and are excluded from the
                    aux losses). Its ``hidden_splits`` (Eq. 2) pad the FFN
                    hidden dim to per-TP-rank MXU-aligned tiles at init
                    (``models.transformer.init_moe_ffn``) with exact zeros
                    in the padded columns. A plan whose splits are uniform
                    short-circuits both mechanisms — the compiled HLO is
                    the uniform path's, bitwise. The plan is static: a
                    replan (runtime.straggler) produces a new plan and a
                    bounded re-trace (parallel.cache.PlanCache).
    """
    mode: str = "hybrid"
    collective_schedule: str = "ag_rs"
    cache_policy: str = "shared_cache"
    remat: str = "block"          # none | block
    blk: int = 128                # expert-sorted layout block size
    impl: Optional[str] = None    # kernel impl override
    fused_ffn: Optional[bool] = None  # fused forward FFN (None = impl default)
    capacity_factor: float = 1.25 # EP baseline only
    scan_layers: bool = True
    forced_layer_mode: Optional[str] = None
    layer_mode_plan: Optional[Tuple[Optional[str], ...]] = None
    device_latencies: Optional[Tuple[float, ...]] = None
    cache_layers: int = 0
    hetero_plan: Optional[Any] = None  # core.hetero.HeteroPlan
    quant: str = "none"           # expert-weight QAT: none | int8 | fp8
    quant_tile: int = 128         # block size of the per-(expert,tile) scales
    # Two-level interconnect (DESIGN.md §10): an ``autotune.Topology``
    # prices the chooser's collectives per level, and on a mesh carrying a
    # "node" axis switches the MoE islands to the hierarchical schedule
    # (two-phase gathers; node-local combine before the cross-node
    # exchange). None, or a mesh without a "node" axis, keeps the flat
    # single-level collectives — bitwise-identical HLO to the pre-topology
    # path.
    topology: Optional[Any] = None  # autotune.Topology
    # Overlap the NEXT layer's expert collectives with the current layer's
    # compute: extends the pipeline-shared cache's double buffering
    # (cache_layers) from fsdp gathers to the data-centric weights' tp
    # factor as well (DESIGN.md §10). Requires cache_layers > 0 and the
    # unrolled layer loop; values are bit-identical to the eager schedule.
    overlap_dispatch: bool = False
    # Router/expert telemetry (DESIGN.md §12): when True the MoE islands
    # return per-expert token counts, capacity drops, and gate-entropy
    # sums as extra jit outputs (obs.device.expert_stats) and
    # models.lm.forward grows a fifth, stats, return element. Default
    # False keeps every return arity — and the compiled HLO — bitwise
    # identical to the uninstrumented path.
    collect_router_stats: bool = False

    def axes(self, mesh: Mesh) -> dict:
        names = list(mesh.axis_names)
        dp = tuple(n for n in ("pod", "data") if n in names)
        # A two-level mesh (DESIGN.md §10) carries a "node" axis: the TP
        # group spans ("node", "model") — node-major, so the flattened rank
        # order (and therefore every gather's concat order) matches the
        # equivalent flat mesh exactly.
        tp: Any = "model" if "model" in names else None
        if tp is not None and "node" in names:
            tp = ("node", "model")
        if self.mode == "model_centric":
            return {"fsdp": (), "tp": tp, "dp": dp, "sp": tp}
        if self.mode == "data_centric":
            # paper §4.3: PURE data parallelism — every device computes its
            # own batch shard; params are sharded over the whole mesh and
            # gathered at use (pipeline-shared cache bounds residency).
            tp_axes = tp if isinstance(tp, tuple) else ((tp,) if tp else ())
            all_axes = dp + tp_axes
            return {"fsdp": all_axes, "tp": None, "dp": all_axes, "sp": None}
        if self.mode in ("hybrid", "ep", "auto"):
            # "auto" uses the hybrid physical layout — the superset both
            # per-layer behaviours execute from: model-centric dispatch moves
            # tokens over "tp", data-centric dispatch gathers the weights'
            # tp factor inside the island instead (DESIGN.md §3).
            return {"fsdp": dp, "tp": tp, "dp": dp, "sp": tp}
        raise ValueError(self.mode)


def resolve_spec(logical: Sequence, cfg: ParallelConfig, mesh: Mesh) -> P:
    """Translate a logical spec tuple into a physical PartitionSpec."""
    table = cfg.axes(mesh)
    out = []
    for entry in logical:
        if entry is None:
            out.append(None)
            continue
        parts = entry if isinstance(entry, tuple) else (entry,)
        phys: list = []
        for p in parts:
            m = table.get(p, p)
            if m is None or m == ():
                continue
            phys.extend(m if isinstance(m, tuple) else (m,))
        # Drop axes whose mesh extent doesn't divide... left to callers; XLA
        # requires divisibility, configs are chosen to satisfy it.
        if not phys:
            out.append(None)
        elif len(phys) == 1:
            out.append(phys[0])
        else:
            out.append(tuple(phys))
    return P(*out)


def _dim_sizes(mesh: Mesh, spec: P) -> list[int]:
    sizes = []
    for entry in spec:
        if entry is None:
            sizes.append(1)
        elif isinstance(entry, tuple):
            sizes.append(int(np.prod([mesh.shape[a] for a in entry])))
        else:
            sizes.append(mesh.shape[entry])
    return sizes


def divisible_spec(shape, spec: P, mesh: Mesh) -> P:
    """Drop spec entries that do not divide the corresponding dim."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep: list = []
        extent = 1
        for a in axes:
            if dim % (extent * mesh.shape[a]) == 0:
                keep.append(a)
                extent *= mesh.shape[a]
        out.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return P(*out)


def tree_shardings(values, logical_specs, cfg: ParallelConfig, mesh: Mesh):
    """NamedShardings for a whole (already split) value tree."""
    def one(v, spec):
        phys = resolve_spec(spec, cfg, mesh)
        phys = divisible_spec(v.shape, phys, mesh)
        return NamedSharding(mesh, phys)
    return jax.tree.map(one, values, logical_specs)


def constrain(x, spec: Sequence, cfg: ParallelConfig, mesh: Optional[Mesh]):
    """with_sharding_constraint via logical names (no-op without a mesh)."""
    if mesh is None:
        return x
    phys = divisible_spec(x.shape, resolve_spec(spec, cfg, mesh), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, phys))


# ---------------------------------------------------------------------------
# initializers (pure, eval_shape friendly)
# ---------------------------------------------------------------------------

def normal_init(key, shape, dtype, scale: float = 0.02):
    """Truncated-free scaled normal init (f32 draw, cast to dtype)."""
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def zeros_init(key, shape, dtype, scale: float = 0.0):
    """All-zeros init (key/scale ignored; kept initializer-signature)."""
    del key, scale
    return jnp.zeros(shape, dtype)


def ones_init(key, shape, dtype, scale: float = 0.0):
    """All-ones init (key/scale ignored; kept initializer-signature)."""
    del key, scale
    return jnp.ones(shape, dtype)
