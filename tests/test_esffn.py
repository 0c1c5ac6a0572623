"""Fused expert-FFN (kernels/esffn.py + ops.esffn_*, DESIGN.md §5) vs the
unfused gather/esmm/act/esmm/combine composition: forward and gradients,
across impls, expert-load shapes, both body types, with and without biases —
plus the cost-model claim that the (Np, F) hidden round-trip is gone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import espec
from repro.core.reindex import build_reindex, gather_rows
from repro.kernels import ops, tiles
from repro.kernels.esffn import (
    esffn_blocks, esffn_cost, esffn_glu_pallas, esffn_mlp_pallas, esffn_vmem)

N, D, F, E, K, BLK = 48, 16, 24, 4, 2, 8
IMPLS = ["pallas", "blocked", "ref"]

#: Expert-load shapes: uniform routing, heavily skewed (uneven per-expert
#: counts), and everything-to-expert-0 (E-1 empty experts + tail blocks).
LOADS = ["uniform", "uneven", "empty"]


def _routing(load, n=N, k=K, e=E, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    if load == "uniform":
        ei = jax.random.randint(ks[0], (n, k), 0, e)
    elif load == "uneven":
        ei = jnp.minimum(
            jax.random.randint(ks[0], (n, k), 0, e),
            jax.random.randint(ks[1], (n, k), 0, e),
        )
    elif load == "empty":
        ei = jnp.zeros((n, k), jnp.int32)
    else:
        raise ValueError(load)
    g = jax.random.uniform(ks[2], (n, k))
    return build_reindex(ei, g, e, BLK)


def _weights(seed=0, e=E, d=D, f=F):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return {
        "w_gate": jax.random.normal(ks[0], (e, d, f)) * 0.2,
        "w_up": jax.random.normal(ks[1], (e, d, f)) * 0.2,
        "w_down": jax.random.normal(ks[2], (e, f, d)) * 0.2,
        "w1": jax.random.normal(ks[3], (e, d, f)) * 0.2,
        "b1": jax.random.normal(ks[4], (e, f)) * 0.2,
        "w2": jax.random.normal(ks[5], (e, f, d)) * 0.2,
        "b2": jax.random.normal(ks[6], (e, d)) * 0.2,
    }


def _x(seed=9, n=N, d=D):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d))


def _run(x, ri, w, glu, bias, impl, fused):
    if glu:
        return espec.moe_glu(
            x, ri, w["w_gate"], w["w_up"], w["w_down"], act="silu",
            impl=impl, fused=fused,
        )
    return espec.moe_mlp(
        x, ri,
        w["w1"], w["b1"] if bias else None,
        w["w2"], w["b2"] if bias else None,
        act="gelu", impl=impl, fused=fused,
    )


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("glu,bias", [(True, False), (False, True),
                                      (False, False)])
@pytest.mark.parametrize("load", LOADS)
def test_fused_forward_matches_unfused(impl, glu, bias, load):
    ri = _routing(load)
    x, w = _x(), _weights()
    want = _run(x, ri, w, glu, bias, "blocked", fused=False)
    got = _run(x, ri, w, glu, bias, impl, fused=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("glu,bias", [(True, False), (False, True),
                                      (False, False)])
@pytest.mark.parametrize("load", ["uniform", "empty"])
def test_fused_grads_match_unfused(impl, glu, bias, load):
    """Full-pipeline grads (x, every weight, and — via the in-kernel gate
    weighting — the routing gates/router) of fused == unfused."""
    ri = _routing(load)
    x, w = _x(), _weights()
    tgt = _x(seed=11)
    keys = (["w_gate", "w_up", "w_down"] if glu
            else (["w1", "b1", "w2", "b2"] if bias else ["w1", "w2"]))

    def loss(x, w, impl, fused):
        y = _run(x, ri, w, glu, bias, impl, fused)
        return jnp.sum((y - tgt) ** 2)

    gx_u, gw_u = jax.grad(loss, argnums=(0, 1))(x, w, "blocked", False)
    gx_f, gw_f = jax.grad(loss, argnums=(0, 1))(x, w, impl, True)
    np.testing.assert_allclose(
        np.asarray(gx_f), np.asarray(gx_u), rtol=5e-4, atol=5e-5
    )
    for key in keys:
        np.testing.assert_allclose(
            np.asarray(gw_f[key]), np.asarray(gw_u[key]),
            rtol=5e-4, atol=5e-5, err_msg=f"{impl} {key}",
        )


@pytest.mark.parametrize("glu", [True, False])
def test_fused_router_grads_match(glu):
    """Gate gradients flow through the fused op's custom_vjp (d_gate) back
    to the router weights — end-to-end through hexa_moe_ffn."""
    p = _weights()
    p["router"] = jax.random.normal(jax.random.PRNGKey(3), (D, E)) * 0.2
    x = _x()
    tgt = _x(seed=12)

    def loss(p, fused, impl):
        out = espec.hexa_moe_ffn(
            x, p, num_experts=E, top_k=K, act="silu" if glu else "gelu",
            glu=glu, blk=BLK, impl=impl, fused=fused,
        )
        return jnp.sum((out.y - tgt) ** 2)

    g_u = jax.grad(loss)(p, False, "blocked")
    for impl in IMPLS:
        g_f = jax.grad(loss)(p, True, impl)
        np.testing.assert_allclose(
            np.asarray(g_f["router"]), np.asarray(g_u["router"]),
            rtol=5e-4, atol=5e-5, err_msg=impl,
        )


def test_fused_empty_expert_weight_grads_zero():
    """Experts that received no tokens must get exactly-zero weight grads
    through the fused backward (recompute path included)."""
    ri = _routing("empty")
    x, w = _x(), _weights()

    def loss(w):
        y = espec.moe_glu(
            x, ri, w["w_gate"], w["w_up"], w["w_down"], act="silu",
            impl="blocked", fused=True,
        )
        return jnp.sum(y ** 2)

    g = jax.grad(loss)({k: w[k] for k in ("w_gate", "w_up", "w_down")})
    for key, val in g.items():
        arr = np.asarray(val)
        assert np.abs(arr[1:]).max() == 0.0, key   # empty experts
        assert np.abs(arr[0]).max() > 0.0, key     # the loaded expert


def test_pallas_kernel_direct_bf16():
    """The megakernel itself (not through espec), bf16 inputs."""
    ri = _routing("uniform")
    w = _weights()
    x = _x().astype(jnp.bfloat16)
    wg = w["w_gate"].astype(jnp.bfloat16)
    wu = w["w_up"].astype(jnp.bfloat16)
    wd = w["w_down"].astype(jnp.bfloat16)
    got = esffn_glu_pallas(
        gather_rows(x, ri.row_token), ri.row_gate, ri.block_expert,
        wg, wu, wd, act="silu",
    )
    assert got.dtype == jnp.bfloat16
    want = ops.esffn_glu(
        x, ri.row_token, ri.row_gate, ri.block_expert, ri.padded_counts,
        wg, wu, wd, act="silu", impl="ref",
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=4e-2, atol=4e-2,
    )


def test_pallas_kernel_hidden_blocking():
    """bf < F forces multi-step hidden accumulation in the kernel grid."""
    ri = _routing("uniform")
    w = _weights()
    x = _x()
    got = esffn_mlp_pallas(
        gather_rows(x, ri.row_token), ri.row_gate, ri.block_expert,
        w["w1"], w["b1"], w["w2"], w["b2"], act="gelu", bf=8,
    )
    want = _run(x, ri, w, glu=False, bias=True, impl="blocked", fused=False)
    # compare at the sorted level: scatter back first
    from repro.core.reindex import scatter_rows
    got_tok = scatter_rows(got, ri.row_token, N)
    np.testing.assert_allclose(
        np.asarray(got_tok), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("case", ["whole", "split"])
@pytest.mark.parametrize("glu", [True, False])
def test_pallas_kernel_tile_rule(monkeypatch, case, glu):
    """The kernel at the tile rule's hidden block (kernels/tiles.py) against
    the oracle, with two experts over 8 row blocks each spanning several
    consecutive blocks. "whole": the rule's own budget, bf = F, the
    expert's weights resident over its blocks; "split": the budget of
    bf = 128, so the 256-wide hidden splits in two."""
    n, d, f, e, blk = 32, 128, 256, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    ri = build_reindex(jax.random.randint(ks[0], (n, 1), 0, e),
                       jax.random.uniform(ks[1], (n, 1)), e, blk)
    be = np.asarray(ri.block_expert)
    assert np.any(be[1:] == be[:-1]), be
    w, x = _weights(e=e, d=d, f=f), _x(n=n, d=d)
    kw = dict(glu=glu, x_itemsize=4, w_itemsize=4, has_b1=not glu,
              has_b2=not glu)
    if case == "split":
        monkeypatch.setattr(tiles, "VMEM_BUDGET",
                            esffn_vmem(blk, d, 128, **kw))
    bf = esffn_blocks(blk, d, f, **kw)
    assert bf == (f if case == "whole" else 128)
    xs = gather_rows(x, ri.row_token)
    blocks = {} if case == "whole" else {"bf": bf}
    if glu:
        got = esffn_glu_pallas(xs, ri.row_gate, ri.block_expert,
                               w["w_gate"], w["w_up"], w["w_down"],
                               act="silu", **blocks)
        want = ops.esffn_glu(
            x, ri.row_token, ri.row_gate, ri.block_expert, ri.padded_counts,
            w["w_gate"], w["w_up"], w["w_down"], act="silu", impl="ref")
    else:
        got = esffn_mlp_pallas(xs, ri.row_gate, ri.block_expert, w["w1"],
                               w["b1"], w["w2"], w["b2"], act="gelu",
                               **blocks)
        want = ops.esffn_mlp(
            x, ri.row_token, ri.row_gate, ri.block_expert, ri.padded_counts,
            w["w1"], w["b1"], w["w2"], w["b2"], act="gelu", impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_cost_estimate_excludes_hidden_roundtrip():
    """The acceptance claim: bytes_accessed of the fused kernel has no
    (Np, F) hidden term — it is exactly rows+weights+gate+output, strictly
    below the unfused pipeline's traffic which round-trips the sorted copy
    and the hidden between stages."""
    np_rows, d, f, nm, isz = 2560, 256, 512, 20, 4
    c = esffn_cost(np_rows, d, f, nm, isz, glu=True)
    rows_io = 2 * np_rows * d * isz
    w_tiles = nm * 3 * d * f * isz
    gate = np_rows * 4
    assert c.bytes_accessed == rows_io + w_tiles + gate
    # doubling F must grow bytes only via the weight tiles, never via an
    # Np*F activation term
    c2 = esffn_cost(np_rows, d, 2 * f, nm, isz, glu=True)
    assert c2.bytes_accessed - c.bytes_accessed == w_tiles
    # and the unfused composition's extra inter-stage HBM traffic (hidden
    # g/u write+read + sorted-copy write+read) is strictly additional
    hidden_roundtrip = 2 * 2 * np_rows * f * isz
    sorted_roundtrip = 2 * np_rows * d * isz
    assert c.bytes_accessed < (
        rows_io + w_tiles + gate + hidden_roundtrip + sorted_roundtrip
    )
    # flops/transcendentals sanity: 3 matmuls + one activation sweep
    assert c.flops == 3 * 2 * np_rows * d * f
    assert c.transcendentals == np_rows * f


def test_default_fused_on_for_pallas_only():
    assert ops.default_fused_ffn("pallas") is True
    assert ops.default_fused_ffn("blocked") is False
    assert ops.default_fused_ffn("ref") is False


def test_autotune_unfused_bytes_shift_crossover():
    """The roofline's unfused activation round-trips inflate the token-
    proportional side: the data-centric crossover must move (weakly) later,
    and latencies never shrink."""
    from repro.parallel import autotune

    d, f, e, k = 1024, 4096, 8, 2
    for tokens in (2 ** i for i in range(4, 18)):
        for mode in ("model_centric", "data_centric"):
            fused = autotune.layer_latency(mode, tokens, d, f, e, k, 16)
            unfused = autotune.layer_latency(
                mode, tokens, d, f, e, k, 16, fused_ffn=False
            )
            assert unfused >= fused
    cf = autotune.crossover_tokens(d, f, e, k, n_dev=16)
    cu = autotune.crossover_tokens(d, f, e, k, n_dev=16, fused_ffn=False)
    assert cf is not None and cu is not None
    assert cu >= cf


@pytest.mark.parametrize("uneven", [False, True])
@pytest.mark.parametrize("mode", ["model_centric", "data_centric"])
def test_autotune_fused_prices_sorted_copy(mode, uneven):
    """The fused FFN still reads the (Np, D) rows one XLA gather wrote, so
    the roofline prices it as the unfused composition minus ONLY the
    (Np, F) hidden round-trip — and above the sorted-copy-free bill."""
    from repro.parallel import autotune

    # Memory-bound: compute and links far faster than HBM.
    hw = autotune.HardwareProfile(peak_flops=1e30, hbm_bw=1e12, link_bw=1e30)
    tokens, d, f, e, k, n = 4096, 1024, 4096, 8, 2, 4
    rows = tokens * k
    srt, hid = 2 * rows * d * 2, 2 * rows * f * 2
    tok, w = tokens * d * 2, e * 2 * d * f * 2

    def lat(fused):
        if uneven:
            return autotune.layer_latency_uneven(
                mode, tokens, d, f, e, k, [1.0] * n, hw=hw, fused_ffn=fused)
        return autotune.layer_latency(mode, tokens, d, f, e, k, n, hw,
                                      fused_ffn=fused)

    if mode == "model_centric":
        want = (w / n + tok + srt) / hw.hbm_bw
    else:
        want = (w + (tok + srt) / n) / hw.hbm_bw
    np.testing.assert_allclose(lat(True), want, rtol=1e-12)
    np.testing.assert_allclose(lat(False) - lat(True), hid / n / hw.hbm_bw,
                               rtol=1e-9)
