"""AdamW with configurable state dtypes and warmup-cosine schedule.

Pure JAX (no optax in this environment). Memory knobs that matter at the
512-chip scale (see EXPERIMENTS.md §Dry-run):

  * ``state_dtype`` — m/v moments in bf16 halve optimizer memory; the
    update math is always f32.
  * ``master_fp32`` — keep an f32 master copy when params are bf16
    (standard mixed-precision training); disable to save 4 bytes/param
    when the model checkpoint dtype is already f32.

Optimizer state inherits each parameter's sharding (same tree structure),
so FSDP-sharded params get FSDP-sharded moments — ZeRO-2/3 for free.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    peak_lr: float = 3e-4
    min_lr: float = 3e-5
    warmup_steps: int = 100
    decay_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    state_dtype: str = "float32"     # moments dtype
    master_fp32: bool = True         # keep f32 master for low-prec params


def schedule(step: jax.Array, cfg: OptimizerConfig) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = cfg.peak_lr * step / max(cfg.warmup_steps, 1)
    prog = jnp.clip(
        (step - cfg.warmup_steps) / max(cfg.decay_steps - cfg.warmup_steps, 1),
        0.0, 1.0,
    )
    cos = cfg.min_lr + 0.5 * (cfg.peak_lr - cfg.min_lr) * (
        1 + jnp.cos(jnp.pi * prog)
    )
    return jnp.where(step < cfg.warmup_steps, warm, cos)


def init_opt_state(params: Any, cfg: OptimizerConfig) -> dict:
    sd = jnp.dtype(cfg.state_dtype)
    # zeros_like keeps a sharded param's sharding; jnp.zeros(p.shape) would
    # put every moment whole on the default device.
    zeros_like = lambda p: jnp.zeros_like(p, dtype=sd)
    state = {
        "m": jax.tree.map(zeros_like, params),
        "v": jax.tree.map(zeros_like, params),
        "step": jnp.zeros((), jnp.int32),
    }
    if cfg.master_fp32:
        needs_master = lambda p: (
            jnp.issubdtype(p.dtype, jnp.floating) and p.dtype != jnp.float32
        )
        state["master"] = jax.tree.map(
            lambda p: p.astype(jnp.float32) if needs_master(p) else None,
            params,
        )
    return state


def global_norm(tree: Any) -> jax.Array:
    leaves = [
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree.leaves(tree)
    ]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


@jax.named_scope("optim.adamw")
def apply_updates(
    params: Any, grads: Any, state: dict, cfg: OptimizerConfig
) -> tuple[Any, dict, dict]:
    """Returns (new_params, new_state, metrics). Every op of the update
    runs under the layer scope ``optim.adamw``."""
    step = state["step"] + 1
    lr = schedule(step, cfg)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
        if cfg.grad_clip > 0 else jnp.float32(1.0)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)
    sd = jnp.dtype(cfg.state_dtype)
    masters = state.get("master")

    def upd(p, g, m, v, master):
        gf = g.astype(jnp.float32) * scale
        mf = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * gf
        vf = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * gf * gf
        update = (mf / b1c) / (jnp.sqrt(vf / b2c) + cfg.eps)
        base = master if master is not None else p.astype(jnp.float32)
        decay = cfg.weight_decay if p.ndim >= 2 else 0.0
        new_base = base - lr * (update + decay * base)
        new_p = new_base.astype(p.dtype)
        new_master = new_base if master is not None else None
        return new_p, mf.astype(sd), vf.astype(sd), new_master

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    if masters is not None:
        flat_ma = treedef.flatten_up_to(masters)
    else:
        flat_ma = [None] * len(flat_p)

    outs = [
        upd(p, g, m, v, ma)
        for p, g, m, v, ma in zip(flat_p, flat_g, flat_m, flat_v, flat_ma)
    ]
    new_params = treedef.unflatten([o[0] for o in outs])
    new_state = {
        "m": treedef.unflatten([o[1] for o in outs]),
        "v": treedef.unflatten([o[2] for o in outs]),
        "step": step,
    }
    if masters is not None:
        new_state["master"] = treedef.unflatten([o[3] for o in outs])
    metrics = {"lr": lr, "grad_norm": gnorm}
    return new_params, new_state, metrics
