"""Small shared utilities used across the framework."""
from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


#: Activation table shared by the espec layer and the fused-FFN kernels
#: (kernels must not import core.espec — it imports kernels.ops).
ACTIVATIONS: dict = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
}


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


#: The checkout root: ``<root>/src/repro/common.py``.
REPO_ROOT = Path(__file__).resolve().parents[2]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(None)
def pallas_interpret_default() -> bool:
    """Pallas kernels compile through Mosaic on a TPU and run in interpret
    mode on the CPU. Any other backend has no lowering for them: error."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas TPU kernels cannot run on backend {backend!r}")
    return backend == "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``:
    a fixed path, because the path is part of the cache key. Entry points
    call this before their first compile; tests never do, so they run with
    the cache off."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def tree_bytes(tree: Any) -> int:
    """Total bytes of all arrays / ShapeDtypeStructs in a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total


def tree_params(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) for l in leaves if hasattr(l, "shape"))


def cast_tree(tree: Any, dtype) -> Any:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )
