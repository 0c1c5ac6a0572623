"""input_specs(): ShapeDtypeStruct stand-ins for every model input.

Weak-type-correct, shardable, zero allocation — the training driver and
the step builders shard against these. The same functions build real
arrays for smoke tests (``concrete=True`` path in tests).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.parallel.sharding import ParallelConfig, divisible_spec, resolve_spec


def _sharded(sds: jax.ShapeDtypeStruct, logical, cfg: ParallelConfig,
             mesh: Optional[Mesh]):
    if mesh is None:
        return sds
    spec = divisible_spec(sds.shape, resolve_spec(logical, cfg, mesh), mesh)
    return jax.ShapeDtypeStruct(
        sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)
    )


def activation_spec(shape3, pcfg: ParallelConfig, mesh: Optional[Mesh]) -> P:
    """Physical spec for (B, S, D) activations (dp, sp, -)."""
    if mesh is None:
        return P(None, None, None)
    return divisible_spec(
        shape3, resolve_spec((("dp",), "sp", None), pcfg, mesh), mesh
    )


def input_specs(
    cfg: ModelConfig,
    shape: ShapeConfig,
    pcfg: ParallelConfig,
    mesh: Optional[Mesh],
) -> dict:
    """Abstract model inputs for one (arch x shape) cell."""
    b, s = shape.global_batch, shape.seq_len
    toks = lambda *shp: jax.ShapeDtypeStruct(shp, jnp.int32)
    f32 = lambda *shp: jax.ShapeDtypeStruct(shp, jnp.float32)
    out = {}

    if shape.kind == "decode":
        s_in = 1
    else:
        s_in = s

    if cfg.frontend == "siglip" and shape.kind != "decode":
        n_patch = cfg.prefix_len
        out["patches"] = _sharded(
            f32(b, n_patch, cfg.frontend_dim), (("dp",), None, None), pcfg, mesh
        )
        out["tokens"] = _sharded(
            toks(b, s_in - n_patch), (("dp",), "sp"), pcfg, mesh
        )
    elif cfg.frontend == "encodec":
        out["embeds"] = _sharded(
            f32(b, s_in, cfg.frontend_dim), (("dp",), "sp", None), pcfg, mesh
        )
        out["cond"] = _sharded(
            f32(b, 64, cfg.cross_d), (("dp",), None, None), pcfg, mesh
        )
    else:
        out["tokens"] = _sharded(toks(b, s_in), (("dp",), "sp"), pcfg, mesh)

    if shape.kind == "train":
        if cfg.num_codebooks > 1:
            out["labels"] = _sharded(
                toks(b, s, cfg.num_codebooks), (("dp",), "sp", None), pcfg, mesh
            )
        else:
            lbl_s = s - cfg.prefix_len if cfg.frontend == "siglip" else s
            out["labels"] = _sharded(toks(b, s), (("dp",), "sp"), pcfg, mesh)
        out["loss_mask"] = _sharded(f32(b, s), (("dp",), "sp"), pcfg, mesh)
    return out


def concrete_inputs(cfg: ModelConfig, shape: ShapeConfig, seed: int = 0) -> dict:
    """Real (small!) arrays matching input_specs — for smoke tests."""
    spec = input_specs(cfg, shape, ParallelConfig(), None)
    rng = np.random.default_rng(seed)

    def make(s):
        if np.issubdtype(s.dtype, np.integer):
            return jnp.asarray(
                rng.integers(0, cfg.vocab_size, size=s.shape), s.dtype
            )
        return jnp.asarray(rng.normal(size=s.shape), s.dtype)

    return {k: make(v) for k, v in spec.items()}
