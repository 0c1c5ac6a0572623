"""Per-kernel allclose sweeps: Pallas (interpret) vs the pure-jnp oracle,
across shapes and dtypes (the deliverable-(c) kernel contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.reindex import build_reindex, gather_sorted
from repro.kernels import ref, tiles
from repro.kernels.esfk import esfk_blocks, esfk_pallas, esfk_vmem
from repro.kernels.esmm import esmm_blocks, esmm_pallas, esmm_vmem
from repro.kernels.ess import ess_pallas
from repro.kernels.estmm import estmm_pallas

SHAPES = [
    # (n_tokens, k, E, D1, D2, blk)
    (32, 1, 2, 16, 32, 8),
    (64, 2, 4, 32, 16, 16),
    (48, 2, 3, 16, 16, 8),
    (16, 4, 8, 32, 64, 8),   # many empty experts likely
    (128, 1, 1, 64, 32, 32),  # single expert
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _setup(n, k, e, d1, d2, blk, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    ei = jax.random.randint(ks[0], (n, k), 0, e)
    g = jax.random.uniform(ks[1], (n, k))
    ri = build_reindex(ei, g, e, blk)
    x = jax.random.normal(ks[2], (n, d1)).astype(dtype)
    xs = gather_sorted(x, ri)
    w = (jax.random.normal(ks[3], (e, d1, d2)) * 0.3).astype(dtype)
    b = (jax.random.normal(ks[4], (e, d2)) * 0.3).astype(dtype)
    dy = jax.random.normal(ks[5], (ri.num_rows, d2)).astype(dtype)
    return ri, xs, w, b, dy


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_esmm_pallas(shape, dtype):
    n, k, e, d1, d2, blk = shape
    ri, xs, w, b, _ = _setup(*shape, dtype)
    out = esmm_pallas(xs, w, b, ri.block_expert, bm=blk, bn=min(128, d2),
                      bk=min(128, d1))
    want = ref.esmm(xs, w, b, ri.block_expert)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("dtype", DTYPES)
def test_esmm_pallas_transposed(shape, dtype):
    # transpose_rhs contracts dy (Np, D2) against w (E, D1, D2) on D2:
    # the backward-dX orientation reuses the forward weight array as-is.
    n, k, e, d1, d2, blk = shape
    ri, xs, w, b, dy = _setup(*shape, dtype)
    out = esmm_pallas(dy, w, None, ri.block_expert, transpose_rhs=True,
                      bm=blk)
    want = ref.esmm(dy, w, None, ri.block_expert, transpose_rhs=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_ess_pallas(shape, dtype):
    n, k, e, d1, d2, blk = shape
    ri, xs, w, b, dy = _setup(*shape, dtype)
    out = ess_pallas(dy, ri.block_expert, ri.padded_counts, bm=blk)
    want = ref.ess(dy.astype(jnp.float32), ri.block_expert, e)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_estmm_pallas(shape, dtype):
    n, k, e, d1, d2, blk = shape
    ri, xs, w, b, dy = _setup(*shape, dtype)
    out = estmm_pallas(xs, dy, ri.block_expert, ri.padded_counts, bm=blk)
    want = ref.estmm(
        xs.astype(jnp.float32), dy.astype(jnp.float32), ri.block_expert, e
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_esfk_fused_matches_unfused(shape, dtype):
    n, k, e, d1, d2, blk = shape
    ri, xs, w, b, dy = _setup(*shape, dtype)
    dw_f, db_f = esfk_pallas(xs, dy, ri.block_expert, ri.padded_counts, bm=blk)
    dw_u = estmm_pallas(xs, dy, ri.block_expert, ri.padded_counts, bm=blk)
    db_u = ess_pallas(dy, ri.block_expert, ri.padded_counts, bm=blk)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_u), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(db_f), np.asarray(db_u), rtol=1e-6)


def test_esfk_empty_expert_grads_zero():
    """Experts with zero routed tokens must get exactly-zero grads."""
    n, k, e, d1, d2, blk = 16, 1, 4, 16, 16, 8
    ei = jnp.zeros((n, k), jnp.int32)  # everything to expert 0
    g = jnp.ones((n, k))
    ri = build_reindex(ei, g, e, blk)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d1))
    xs = gather_sorted(x, ri)
    dy = jax.random.normal(jax.random.PRNGKey(1), (ri.num_rows, d2))
    dw, db = esfk_pallas(xs, dy, ri.block_expert, ri.padded_counts, bm=blk)
    assert np.abs(np.asarray(dw[1:])).max() == 0.0
    assert np.abs(np.asarray(db[1:])).max() == 0.0
    assert np.abs(np.asarray(dw[0])).max() > 0.0


# The tile rule's blocks (kernels/tiles.py), in interpret mode against the
# oracle: (n_tokens, k, E, D1, D2, blk) with two experts over 8 row blocks,
# so each expert spans several consecutive blocks. "whole": the rule's own
# budget, whole-expert blocks; "split": the budget of 128 x 128 blocks, so
# it splits 256-wide dims into 128s.
RULE_SHAPE = (64, 1, 2, 256, 256, 8)
RULE_CASES = ["whole", "split"]


def _consecutive_blocks(ri):
    be = np.asarray(ri.block_expert)
    assert np.any(be[1:] == be[:-1]), be


def _rule_blocks(monkeypatch, case, blocks_fn, vmem_fn, bm, d1, d2, **kw):
    if case == "split":
        monkeypatch.setattr(tiles, "VMEM_BUDGET", vmem_fn(bm, 128, 128, **kw))
    got = blocks_fn(bm, d1, d2, **kw)
    assert got == ((d1, d2) if case == "whole" else (128, 128))
    return got


@pytest.mark.parametrize("case", RULE_CASES)
@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("dtype", DTYPES)
def test_esmm_pallas_tile_rule(monkeypatch, case, transpose, dtype):
    n, k, e, d1, d2, blk = RULE_SHAPE
    ri, xs, w, b, dy = _setup(*RULE_SHAPE, dtype)
    _consecutive_blocks(ri)
    x, bias = (dy, None) if transpose else (xs, b)
    isz = jnp.dtype(dtype).itemsize
    bk, bn = _rule_blocks(monkeypatch, case, esmm_blocks, esmm_vmem, blk,
                          x.shape[1], d1 if transpose else d2,
                          x_itemsize=isz, w_itemsize=isz)
    # "whole" runs at the kernel's default blocks, "split" names them.
    blocks = {} if case == "whole" else {"bk": bk, "bn": bn}
    out = esmm_pallas(x, w, bias, ri.block_expert, transpose_rhs=transpose,
                      bm=blk, **blocks)
    want = ref.esmm(x, w, bias, ri.block_expert, transpose_rhs=transpose)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("case", RULE_CASES)
@pytest.mark.parametrize("load", ["uniform", "empty"])
def test_esfk_pallas_tile_rule(monkeypatch, case, load):
    """dW and db against the oracle at the rule's blocks; with everything
    routed to expert 0 the other experts' grads are exact zeros."""
    n, k, e, d1, d2, blk = RULE_SHAPE
    ri, xs, w, b, dy = _setup(*RULE_SHAPE, jnp.float32)
    if load == "empty":
        ri = build_reindex(jnp.zeros((n, k), jnp.int32), jnp.ones((n, k)),
                           e, blk)
    # Padding rows are zero rows, as the layout and the backward give them.
    real = (ri.row_token < n)[:, None]
    xs, dy = jnp.where(real, xs, 0.0), jnp.where(real, dy, 0.0)
    _consecutive_blocks(ri)
    b1, b2 = _rule_blocks(monkeypatch, case, esfk_blocks, esfk_vmem, blk,
                          d1, d2, itemsize1=4, itemsize2=4)
    blocks = {} if case == "whole" else {"b1": b1, "b2": b2}
    dw, db = esfk_pallas(xs, dy, ri.block_expert, ri.padded_counts, bm=blk,
                         **blocks)
    np.testing.assert_allclose(
        np.asarray(dw), np.asarray(ref.estmm(xs, dy, ri.block_expert, e)),
        **_tol(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(db), np.asarray(ref.ess(dy, ri.block_expert, e)),
        **_tol(jnp.float32))
    if load == "empty":
        assert np.abs(np.asarray(dw[1:])).max() == 0.0
        assert np.abs(np.asarray(db[1:])).max() == 0.0
        assert np.abs(np.asarray(dw[0])).max() > 0.0


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_pallas_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """Interpret mode is the CPU's path alone; a backend with no Mosaic
    lowering is an error, never a silent interpreter."""
    from repro import common

    monkeypatch.setattr(common.jax, "default_backend", lambda: backend)
    common.pallas_interpret_default.cache_clear()
    try:
        if interpret is None:
            with pytest.raises(RuntimeError, match="cannot run"):
                common.pallas_interpret_default()
        else:
            assert common.pallas_interpret_default() is interpret
    finally:
        common.pallas_interpret_default.cache_clear()


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set; otherwise the
    cache is pinned to ``<repo>/.jax_cache``. (The config update is
    recorded, not applied: tests keep the cache off.)"""
    from repro import common

    calls = []
    monkeypatch.setattr(common.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = common.enable_compile_cache()
        assert path == str(common.REPO_ROOT / ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", path)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
        assert common.enable_compile_cache() == str(tmp_path / env)
        assert calls == []


def _entry_points():
    """One call per public expert-op and paged-attention entry point, each
    taking the kernel path by name."""
    from repro.kernels import ops
    from repro.kernels.paged_attention import paged_attention

    ri, xs, w, b, dy = _setup(16, 1, 2, 16, 16, 8, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    wt = jnp.swapaxes(w, 1, 2)
    groups = (ri.block_expert, ri.padded_counts)
    rows = (x, ri.row_token, ri.row_gate, *groups)
    pool = jnp.zeros((5, 8, 2, 16))
    table = jnp.arange(1, 5, dtype=jnp.int32).reshape(2, 2)
    return {
        "esmm": lambda impl: ops.esmm(xs, w, b, *groups, impl=impl),
        "esfk": lambda impl: ops.esfk(xs, dy, *groups, impl=impl),
        "ess": lambda impl: ops.ess(dy, *groups, impl=impl),
        "estmm": lambda impl: ops.estmm(xs, dy, *groups, impl=impl),
        "esffn_glu": lambda impl: ops.esffn_glu(*rows, w, w, wt, impl=impl),
        "esffn_mlp": lambda impl: ops.esffn_mlp(*rows, w, b, wt, b,
                                                impl=impl),
        "paged_attention": lambda impl: paged_attention(
            jnp.zeros((2, 1, 4, 16)), pool, pool, table,
            jnp.array([3, 9], jnp.int32), impl=impl),
    }


@pytest.mark.parametrize("entry", ["esmm", "esfk", "ess", "estmm",
                                   "esffn_glu", "esffn_mlp",
                                   "paged_attention"])
def test_unknown_impl_is_refused(entry):
    """Each public entry point runs ``pallas``, ``blocked`` or ``ref`` and
    refuses any other name rather than falling back to a slower path."""
    call = _entry_points()[entry]
    call("ref")  # the same arguments run under a known impl
    with pytest.raises(ValueError, match="unknown"):
        call("ragged")
