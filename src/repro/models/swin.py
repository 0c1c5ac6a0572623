"""Swin-Transformer-MoE — the paper's benchmark model (§5, Tutel setup).

Hierarchical windowed-attention vision transformer with the FFN of
alternating blocks in the last two stages replaced by an MoE FFN. The MoE
FFN uses the paper's 2-MLP expert form (GeLU between, with biases) — i.e.
exactly the formulation of Fig. 3 — through any of the execution paths:

  moe_impl="hexa"        expert-specific ops (the paper's method)
  moe_impl="tutel"       dispatch/combine with capacity factor (baseline)
  moe_impl="megablocks"  worst-case-capacity grouped dense GeMM (baseline)

Simplification vs. the reference Swin: shifted windows are implemented by
rolling without the cross-window attention mask (systems-benchmark fidelity:
identical FLOPs/memory/communication, slightly different masking semantics).

Each part of a block lowers under a layer scope (``jax.named_scope``):
``swin.attention`` (the shift rolls and window attention), ``swin.norm``
(every LayerNorm) and ``swin.mlp`` (the dense MLP). A scope changes the
``op_name`` metadata of the HLO ops it covers, forward and backward, and
not what they compute, so a device trace can attribute each op to its layer.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core import baselines, espec
from repro.core.routing import route
from repro.kernels.window_attention import window_attention
from repro.parallel.moe_parallel import (
    MOE_PARAM_LOGICAL,
    MoEParams,
    MoEStatic,
    moe_layer,
)
from repro.parallel.sharding import ParallelConfig, Param, normal_init


@dataclasses.dataclass(frozen=True)
class SwinConfig:
    name: str
    family: str = "vision-moe"
    img_size: int = 224
    patch_size: int = 4
    in_chans: int = 3
    depths: Tuple[int, ...] = (2, 2, 18, 2)
    dims: Tuple[int, ...] = (96, 192, 384, 768)
    heads: Tuple[int, ...] = (3, 6, 12, 24)
    window: int = 7
    mlp_ratio: float = 4.0
    num_classes: int = 1000
    moe_stages: Tuple[int, ...] = (2, 3)
    moe: Optional[MoEConfig] = None
    norm_eps: float = 1e-5
    dtype: str = "float32"

    def is_moe_block(self, stage: int, blk: int) -> bool:
        return self.moe is not None and stage in self.moe_stages and blk % 2 == 1


SWIN_SMALL = dict(depths=(2, 2, 18, 2), dims=(96, 192, 384, 768),
                  heads=(3, 6, 12, 24))
SWIN_BASE = dict(depths=(2, 2, 18, 2), dims=(128, 256, 512, 1024),
                 heads=(4, 8, 16, 32))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_ln(d):
    return {
        "scale": Param(jnp.ones((d,), jnp.float32), (None,)),
        "bias": Param(jnp.zeros((d,), jnp.float32), (None,)),
    }


def _init_window_attn(key, dim, heads, window, dtype):
    ks = jax.random.split(key, 3)
    return {
        "qkv_w": Param(normal_init(ks[0], (dim, 3 * dim), dtype), ("fsdp", "tp")),
        "qkv_b": Param(jnp.zeros((3 * dim,), jnp.float32), ("tp",)),
        "proj_w": Param(normal_init(ks[1], (dim, dim), dtype), ("tp", "fsdp")),
        "proj_b": Param(jnp.zeros((dim,), jnp.float32), (None,)),
        "rel_bias": Param(
            normal_init(ks[2], ((2 * window - 1) ** 2, heads), jnp.float32),
            (None, None),
        ),
    }


def _init_mlp(key, dim, hidden, dtype):
    ks = jax.random.split(key, 2)
    return {
        "w1": Param(normal_init(ks[0], (dim, hidden), dtype), ("fsdp", "tp")),
        "b1": Param(jnp.zeros((hidden,), jnp.float32), ("tp",)),
        "w2": Param(normal_init(ks[1], (hidden, dim), dtype), ("tp", "fsdp")),
        "b2": Param(jnp.zeros((dim,), jnp.float32), (None,)),
    }


def _init_moe_mlp(key, dim, hidden, moe: MoEConfig, dtype):
    ks = jax.random.split(key, 3)
    e = moe.num_experts
    L = MOE_PARAM_LOGICAL
    return {
        "router": Param(normal_init(ks[0], (dim, e), jnp.float32), L["router"]),
        "w1": Param(normal_init(ks[1], (e, dim, hidden), dtype), L["w1"]),
        "b1": Param(jnp.zeros((e, hidden), jnp.float32), L["b1"]),
        "w2": Param(normal_init(ks[2], (e, hidden, dim), dtype), L["w2"]),
        "b2": Param(jnp.zeros((e, dim), jnp.float32), L["b2"]),
    }


def init_swin(key, cfg: SwinConfig):
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 128)
    ki = iter(range(128))
    p: dict = {
        "patch_w": Param(
            normal_init(
                keys[next(ki)],
                (cfg.patch_size, cfg.patch_size, cfg.in_chans, cfg.dims[0]),
                dtype,
            ),
            (None, None, None, None),
        ),
        "patch_b": Param(jnp.zeros((cfg.dims[0],), jnp.float32), (None,)),
        "patch_ln": _init_ln(cfg.dims[0]),
        "stages": [],
        "final_ln": _init_ln(cfg.dims[-1]),
        "head_w": Param(
            normal_init(keys[next(ki)], (cfg.dims[-1], cfg.num_classes), dtype),
            (None, None),
        ),
        "head_b": Param(jnp.zeros((cfg.num_classes,), jnp.float32), (None,)),
    }
    for s, depth in enumerate(cfg.depths):
        dim, heads = cfg.dims[s], cfg.heads[s]
        hidden = int(cfg.mlp_ratio * dim)
        blocks = []
        for b in range(depth):
            blk = {
                "ln1": _init_ln(dim),
                "attn": _init_window_attn(
                    keys[next(ki)], dim, heads, cfg.window, dtype
                ),
                "ln2": _init_ln(dim),
            }
            if cfg.is_moe_block(s, b):
                blk["moe"] = _init_moe_mlp(
                    keys[next(ki)], dim, hidden, cfg.moe, dtype
                )
            else:
                blk["mlp"] = _init_mlp(keys[next(ki)], dim, hidden, dtype)
            blocks.append(blk)
        stage = {"blocks": blocks}
        if s < len(cfg.depths) - 1:
            stage["merge_w"] = Param(
                normal_init(keys[next(ki)], (4 * dim, 2 * dim), dtype),
                ("fsdp", "tp"),
            )
            stage["merge_ln"] = _init_ln(4 * dim)
        p["stages"].append(stage)
    return p


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def _ln(p, x, eps):
    with jax.named_scope("swin.norm"):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"]
                + p["bias"]).astype(x.dtype)


def _rel_bias_index(window):
    coords = jnp.stack(
        jnp.meshgrid(jnp.arange(window), jnp.arange(window), indexing="ij"), -1
    ).reshape(-1, 2)
    rel = coords[:, None] - coords[None, :] + window - 1  # (w2, w2, 2)
    return rel[..., 0] * (2 * window - 1) + rel[..., 1]


def _window_attention(p, x, heads, window, eps):
    """x: (B, H, W, C) -> same, windowed MSA. The partition, the two
    projections and the reverse are XLA; the attention between the
    projections is one Pallas kernel (``kernels.window_attention``)."""
    b, h, w, c = x.shape
    window = min(window, h, w)  # Swin clamps when window > feature map
    nwh, nww = h // window, w // window
    xw = x.reshape(b, nwh, window, nww, window, c)
    # (windows x w2 tokens, C): window after window.
    xw = xw.transpose(0, 1, 3, 2, 4, 5).reshape(-1, c)

    qkv = xw @ p["qkv_w"].astype(xw.dtype) + p["qkv_b"].astype(xw.dtype)
    bias = p["rel_bias"][_rel_bias_index(window)]  # (w2, w2, heads)
    out = window_attention(qkv, bias, heads)
    out = out @ p["proj_w"].astype(xw.dtype) + p["proj_b"].astype(xw.dtype)

    out = out.reshape(b, nwh, nww, window, window, c)
    return out.transpose(0, 1, 3, 2, 4, 5).reshape(b, h, w, c)


def _apply_moe_ffn(p, x_tokens, cfg: SwinConfig, pcfg, mesh, moe_impl, x_spec):
    """x_tokens: (B, L, C). Returns (y, aux, z)."""
    m = cfg.moe
    if moe_impl == "hexa":
        ms = MoEStatic(
            num_experts=m.num_experts, top_k=m.top_k, act="gelu", glu=False,
            norm_topk=m.norm_topk, softmax_after_topk=m.softmax_after_topk,
        )
        mp = MoEParams(router=p["router"], w1=p["w1"], b1=p["b1"],
                       w2=p["w2"], b2=p["b2"])
        if pcfg.collect_router_stats:
            # Router telemetry is an LM-stack feature; the vision tower
            # keeps the plain 3-tuple contract.
            pcfg = dataclasses.replace(pcfg, collect_router_stats=False)
        return moe_layer(x_tokens, mp, ms, pcfg, mesh, x_spec=x_spec)
    bsz, L, c = x_tokens.shape
    xf = x_tokens.reshape(bsz * L, c)
    r = route(xf, p["router"], m.top_k, norm_topk=m.norm_topk,
              softmax_after_topk=m.softmax_after_topk)
    if moe_impl == "tutel":
        y = baselines.dispatch_combine_moe(
            xf, r, p["w1"], p["b1"], p["w2"], p["b2"], act=jax.nn.gelu,
            capacity_factor=pcfg.capacity_factor,
        )
    elif moe_impl == "megablocks":
        y = baselines.grouped_dense_moe(
            xf, r, p["w1"], p["b1"], p["w2"], p["b2"], act=jax.nn.gelu,
        )
    else:
        raise ValueError(moe_impl)
    return y.reshape(bsz, L, c), r.aux_loss, r.z_loss


def swin_forward(
    params,
    images: jax.Array,
    cfg: SwinConfig,
    pcfg: ParallelConfig,
    mesh: Optional[Mesh] = None,
    *,
    moe_impl: str = "hexa",
):
    """images: (B, H, W, 3) -> (logits (B, classes), aux, z)."""
    dtype = jnp.dtype(cfg.dtype)
    x = jax.lax.conv_general_dilated(
        images.astype(dtype),
        params["patch_w"].astype(dtype),
        window_strides=(cfg.patch_size, cfg.patch_size),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ) + params["patch_b"].astype(dtype)
    x = _ln(params["patch_ln"], x, cfg.norm_eps)

    aux_total = jnp.zeros((), jnp.float32)
    z_total = jnp.zeros((), jnp.float32)
    n_moe = 0
    for s, stage in enumerate(params["stages"]):
        heads = cfg.heads[s]
        for bidx, blk in enumerate(stage["blocks"]):
            w_eff = min(cfg.window, x.shape[1], x.shape[2])
            shift = (w_eff // 2) if (bidx % 2 == 1 and w_eff < x.shape[1]) else 0
            h = _ln(blk["ln1"], x, cfg.norm_eps)
            with jax.named_scope("swin.attention"):
                if shift:
                    h = jnp.roll(h, (-shift, -shift), axis=(1, 2))
                h = _window_attention(blk["attn"], h, heads, cfg.window,
                                      cfg.norm_eps)
                if shift:
                    h = jnp.roll(h, (shift, shift), axis=(1, 2))
            x = x + h
            h = _ln(blk["ln2"], x, cfg.norm_eps)
            bb, hh, ww, cc = h.shape
            if "moe" in blk:
                y, aux, z = _apply_moe_ffn(
                    blk["moe"], h.reshape(bb, hh * ww, cc), cfg, pcfg, mesh,
                    moe_impl, P(("pod", "data") if mesh else None, None, None),
                )
                y = y.reshape(bb, hh, ww, cc)
                aux_total += aux
                z_total += z
                n_moe += 1
            else:
                m = blk["mlp"]
                with jax.named_scope("swin.mlp"):
                    y = jax.nn.gelu(
                        h @ m["w1"].astype(dtype) + m["b1"].astype(dtype)
                    ) @ m["w2"].astype(dtype) + m["b2"].astype(dtype)
            x = x + y
        if "merge_w" in stage:
            bb, hh, ww, cc = x.shape
            x = x.reshape(bb, hh // 2, 2, ww // 2, 2, cc)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(bb, hh // 2, ww // 2, 4 * cc)
            x = _ln(stage["merge_ln"], x, cfg.norm_eps)
            x = x @ stage["merge_w"].astype(dtype)

    x = _ln(params["final_ln"], x, cfg.norm_eps)
    pooled = x.mean(axis=(1, 2)).astype(jnp.float32)
    logits = pooled @ params["head_w"].astype(jnp.float32) + params["head_b"]
    denom = max(n_moe, 1)
    return logits, aux_total / denom, z_total / denom
