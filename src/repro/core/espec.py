"""Layer-level expert-specific MoE computation (paper Fig. 3, in-place form).

The full pipeline for one MoE FFN:

  route -> build_reindex -> gather_sorted -> ESMM -> act -> ESMM -> combine

with zero computation redundancy: no capacity factor, no token drop, at most
BLK-1 pad rows per expert. Autodiff flows through the custom-vjp'd ``esmm``
(dX via ESMM, dW/db via the fused ESFK), i.e. exactly the paper's Table 5.

With ``fused`` on (default for the TPU ``pallas`` impl) the gather/ESMM/act/
ESMM/gate stages collapse into ONE fused-FFN op (``kernels.ops.esffn_*``,
the Pallas megakernel of DESIGN.md §5): token rows are gathered straight
from the unsorted activations, the (Np, F) hidden never touches HBM, and
only the final scatter-add combine remains outside.

Two expert body types are supported:
  * ``moe_mlp`` — the paper's 2-MLP expert (Swin-MoE, classic GShard FFN).
  * ``moe_glu`` — gate/up/down GLU experts (Mixtral / Qwen3 / Jamba).

Both lower their parts under layer scopes (``jax.named_scope``), which the
HLO ops keep in their ``op_name`` metadata, forward and backward:
``moe.experts`` (the expert FFN with its row gather) and ``moe.combine``;
the callers route under ``moe.route``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax

from repro.common import ACTIVATIONS
from repro.core.reindex import (
    ReIndex,
    build_reindex,
    combine_scatter,
    gather_sorted,
    scatter_rows,
)
from repro.core.routing import RouterOutput, route
from repro.kernels import ops

__all__ = [
    "ACTIVATIONS", "MoEOutput", "hexa_moe_ffn", "moe_glu", "moe_mlp",
]


def moe_mlp(
    x: jax.Array,
    ri: ReIndex,
    w1: jax.Array,
    b1: Optional[jax.Array],
    w2: jax.Array,
    b2: Optional[jax.Array],
    *,
    scales=None,
    act: str = "gelu",
    impl: Optional[str] = None,
    fused: Optional[bool] = None,
) -> jax.Array:
    """Paper-form 2-MLP expert FFN over a flat token batch x: (N, D).

    ``scales``: (s1, s2) block-wise scales when w1/w2 are int8/fp8
    payloads (DESIGN.md §8) — dequant fuses into the ES kernels."""
    impl = impl or ops.get_default_impl()
    if fused is None:
        fused = ops.default_fused_ffn(impl)
    if fused:
        with jax.named_scope("moe.experts"):
            ys = ops.esffn_mlp(
                x, ri.row_token, ri.row_gate, ri.block_expert,
                ri.padded_counts, w1, b1, w2, b2, scales=scales, act=act,
                impl=impl,
            )
        with jax.named_scope("moe.combine"):
            return scatter_rows(ys, ri.row_token, x.shape[0])
    f = ACTIVATIONS[act]
    s1, s2 = scales if scales is not None else (None, None)
    with jax.named_scope("moe.experts"):
        xs = gather_sorted(x, ri)
        h = ops.esmm(xs, w1, b1, ri.block_expert, ri.padded_counts,
                     impl=impl, w_scales=s1)
        h = f(h)
        ys = ops.esmm(h, w2, b2, ri.block_expert, ri.padded_counts,
                      impl=impl, w_scales=s2)
    with jax.named_scope("moe.combine"):
        return combine_scatter(ys, ri, x.shape[0])


def moe_glu(
    x: jax.Array,
    ri: ReIndex,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    scales=None,
    act: str = "silu",
    impl: Optional[str] = None,
    fused: Optional[bool] = None,
) -> jax.Array:
    """GLU expert FFN: y = (act(x Wg) * (x Wu)) Wd, routed per token.

    ``scales``: (sg, su, sd) block-wise scales when the weights are
    int8/fp8 payloads (DESIGN.md §8)."""
    impl = impl or ops.get_default_impl()
    if fused is None:
        fused = ops.default_fused_ffn(impl)
    if fused:
        with jax.named_scope("moe.experts"):
            ys = ops.esffn_glu(
                x, ri.row_token, ri.row_gate, ri.block_expert,
                ri.padded_counts, w_gate, w_up, w_down, scales=scales,
                act=act, impl=impl,
            )
        with jax.named_scope("moe.combine"):
            return scatter_rows(ys, ri.row_token, x.shape[0])
    f = ACTIVATIONS[act]
    sg, su, sd = scales if scales is not None else (None, None, None)
    with jax.named_scope("moe.experts"):
        xs = gather_sorted(x, ri)
        g = ops.esmm(xs, w_gate, None, ri.block_expert, ri.padded_counts,
                     impl=impl, w_scales=sg)
        u = ops.esmm(xs, w_up, None, ri.block_expert, ri.padded_counts,
                     impl=impl, w_scales=su)
        h = f(g) * u
        ys = ops.esmm(h, w_down, None, ri.block_expert, ri.padded_counts,
                      impl=impl, w_scales=sd)
    with jax.named_scope("moe.combine"):
        return combine_scatter(ys, ri, x.shape[0])


class MoEOutput(NamedTuple):
    y: jax.Array
    aux_loss: jax.Array
    z_loss: jax.Array
    router: RouterOutput


def hexa_moe_ffn(
    x: jax.Array,
    params: dict,
    *,
    num_experts: int,
    top_k: int,
    act: str,
    glu: bool,
    blk: int = 128,
    norm_topk: bool = True,
    softmax_after_topk: bool = False,
    noise_rng: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    fused: Optional[bool] = None,
) -> MoEOutput:
    """Complete Hexa-MoE FFN: routing + expert-specific computation.

    x: (N, D) flat tokens. params holds 'router' (D, E) plus either
    {'w1','b1','w2','b2'} (mlp) or {'w_gate','w_up','w_down'} (glu);
    quantized expert weights carry their block scales as '<name>_scale'
    entries (quant.core.quantize_ffn, DESIGN.md §8) and are detected here.
    """
    r = route(
        x,
        params["router"],
        top_k,
        norm_topk=norm_topk,
        softmax_after_topk=softmax_after_topk,
        noise_rng=noise_rng,
    )
    ri = build_reindex(r.expert_idx, r.gates, num_experts, blk)
    if glu:
        scales = None
        if "w_gate_scale" in params:
            scales = (params["w_gate_scale"], params["w_up_scale"],
                      params["w_down_scale"])
        y = moe_glu(
            x,
            ri,
            params["w_gate"],
            params["w_up"],
            params["w_down"],
            scales=scales,
            act=act,
            impl=impl,
            fused=fused,
        )
    else:
        scales = None
        if "w1_scale" in params:
            scales = (params["w1_scale"], params["w2_scale"])
        y = moe_mlp(
            x,
            ri,
            params["w1"],
            params.get("b1"),
            params["w2"],
            params.get("b2"),
            scales=scales,
            act=act,
            impl=impl,
            fused=fused,
        )
    return MoEOutput(y=y, aux_loss=r.aux_loss, z_loss=r.z_loss, router=r)
