"""Quantized-expert subsystem (ISSUE 5, DESIGN.md §8): block-wise
int8/fp8 quant/dequant exactness, STE gradient flow, fused-dequant
esffn/esmm parity against the dequant-then-dense reference across
pallas-interpret/blocked/ref, uneven expert loads, the
weight_bits cost-model terms, precision-aware hetero execution, and the
island-level QAT / true-quant paths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hetero import HeteroPlan, make_hetero_plan
from repro.core.reindex import build_reindex
from repro.core.routing import route
from repro.kernels import ops
from repro.kernels.esffn import esffn_cost
from repro.parallel import autotune
from repro.parallel.hetero_exec import HeteroExecutor
from repro.parallel.moe_parallel import MoEParams, MoEStatic, moe_layer
from repro.parallel.sharding import ParallelConfig
from repro.quant import core as qc

IMPLS = ("pallas", "blocked", "ref")


def _setup(seed=0, n=24, d=32, f=48, e=4, k=2, blk=8, glu=True):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e)), jnp.float32)
    r = route(x, router, k)
    ri = build_reindex(r.expert_idx, r.gates, e, blk)
    if glu:
        ws = tuple(
            jnp.asarray(rng.normal(size=s), jnp.float32)
            for s in ((e, d, f), (e, d, f), (e, f, d))
        )
    else:
        ws = (
            jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32),
            jnp.asarray(rng.normal(size=(e, f)), jnp.float32),
            jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(e, d)), jnp.float32),
        )
    return x, ri, ws


# ---------------------------------------------------------------------------
# core quant/dequant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_roundtrip_exact_on_representable_grid(mode):
    """quantize∘dequantize is idempotent: values already on a block's grid
    survive a second round-trip bit-exactly, and each block's amax maps to
    the top code exactly."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(3, 64, 32)), jnp.float32)
    q, s = qc.quantize_blockwise(w, mode=mode, tile=16)
    w1 = qc.dequantize_blockwise(q, s)
    q2, s2 = qc.quantize_blockwise(w1, mode=mode, tile=16)
    w2 = qc.dequantize_blockwise(q2, s2)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    # per-block amax is exactly representable (|amax| -> qmax * scale)
    np.testing.assert_allclose(
        np.max(np.abs(np.asarray(w1)), axis=(1, 2)),
        np.max(np.abs(np.asarray(w)), axis=(1, 2)), rtol=1e-6)


def test_int8_error_bounded_by_half_step():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(2, 32, 32)), jnp.float32)
    q, s = qc.quantize_blockwise(w, tile=32)
    err = np.abs(np.asarray(qc.dequantize_blockwise(q, s) - w))
    step = np.asarray(s)[:, :, None, None]  # one scale per whole block here
    assert (err <= 0.5 * step.reshape(2, 1, 1) + 1e-7).all()


def test_scales_shape_and_tile_clamping():
    w = jnp.ones((5, 2, 256, 48))
    q, s = qc.quantize_blockwise(w, tile=128)
    assert q.shape == w.shape and q.dtype == jnp.int8
    assert s.shape == (5, 2, 2, 1)  # 256/128 x 48/min(128,48)
    with pytest.raises(ValueError):
        qc.quantize_blockwise(jnp.ones((100, 48)), tile=64)  # 100 % 64 != 0


def test_stochastic_rounding_unbiased():
    """floor(x/s + u) averages to x/s over draws (the deterministic round
    would be off by the sub-step fraction)."""
    x = jnp.full((8, 8), 0.3)  # between int steps for scale ~ 1/127*amax...
    q, s = qc.quantize_blockwise(x, tile=8)  # amax==x -> code 127 exactly
    # use a value grid with a genuine fractional code instead
    w = jnp.asarray([[1.0, 0.3]] * 4, jnp.float32)  # scale = 1/127
    codes = []
    for i in range(300):
        q, s = qc.quantize_blockwise(w, tile=4, rng=jax.random.PRNGKey(i))
        codes.append(np.asarray(q, np.float64))
    mean_code = np.stack(codes).mean(0)
    target = np.asarray(w) / np.asarray(qc._upsample(s, w.shape))
    assert np.abs(mean_code - target).max() < 0.12  # ~0.5/sqrt(300) * 3σ


def test_ste_gradient_is_identity():
    w = jnp.asarray(np.random.default_rng(2).normal(size=(4, 16, 16)),
                    jnp.float32)
    c = jnp.asarray(np.random.default_rng(3).normal(size=w.shape), jnp.float32)
    g = jax.grad(lambda w_: jnp.sum(qc.fake_quant(w_, "int8", 16) * c))(w)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(c))


def test_kv_row_roundtrip():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(6, 4, 16)), jnp.float32)
    q, s = qc.quantize_rows(x)
    assert q.dtype == jnp.int8 and s.shape == (6, 4)
    err = np.abs(np.asarray(qc.dequantize_rows(q, s) - x))
    assert err.max() <= 0.5 * np.asarray(s).max() + 1e-7


def test_compression_reexports_are_the_same_objects():
    """One rounding convention repo-wide: optim.compression re-exports the
    quant.core primitives (satellite: unify quant primitives)."""
    from repro.optim import compression

    assert compression.quantize_int8 is qc.quantize_int8
    assert compression.dequantize_int8 is qc.dequantize_int8
    # the error-feedback path still round-trips exactly on its own output
    rec, res = compression.compress_roundtrip(
        jnp.asarray([[0.5, -1.0, 2.0]], jnp.float32))
    rec2, _ = compression.compress_roundtrip(rec)
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(rec2))


# ---------------------------------------------------------------------------
# fused-dequant kernels == dequant-then-dense reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
def test_esffn_glu_quantized_matches_reference(impl):
    """Fused-dequant GLU forward AND grads (x, gate) are exactly the
    dequant-then-dense reference in f32 — the in-kernel VMEM dequant
    computes the same f32 weight values the reference materialises."""
    x, ri, (wg, wu, wd) = _setup(seed=5)
    (qg, sg), (qu, su), (qd, sd) = (qc.quantize_blockwise(w)
                                    for w in (wg, wu, wd))
    dg, du, dd = (qc.dequantize_blockwise(q, s)
                  for q, s in ((qg, sg), (qu, su), (qd, sd)))

    def f_q(x_, gate_):
        y = ops.esffn_glu(x_, ri.row_token, gate_, ri.block_expert,
                          ri.padded_counts, qg, qu, qd,
                          scales=(sg, su, sd), impl=impl)
        return jnp.sum(y * y), y

    def f_r(x_, gate_):
        y = ops.esffn_glu(x_, ri.row_token, gate_, ri.block_expert,
                          ri.padded_counts, dg, du, dd, impl=impl)
        return jnp.sum(y * y), y

    (lq, yq), gq = jax.value_and_grad(f_q, argnums=(0, 1), has_aux=True)(
        x, ri.row_gate)
    (lr, yr), gr = jax.value_and_grad(f_r, argnums=(0, 1), has_aux=True)(
        x, ri.row_gate)
    np.testing.assert_array_equal(np.asarray(yq), np.asarray(yr))
    for a, b in zip(gq, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("impl", IMPLS)
def test_esffn_mlp_quantized_matches_reference(impl):
    """Quantized 2-MLP fused op: forward + dx/dgate/db1/db2 match the
    dequant reference (biases stay full precision, so their grads flow)."""
    x, ri, (w1, b1, w2, b2) = _setup(seed=6, glu=False)
    (q1, s1), (q2, s2) = (qc.quantize_blockwise(w) for w in (w1, w2))
    d1, d2 = (qc.dequantize_blockwise(q, s) for q, s in ((q1, s1), (q2, s2)))

    def f_q(x_, gate_, b1_, b2_):
        y = ops.esffn_mlp(x_, ri.row_token, gate_, ri.block_expert,
                          ri.padded_counts, q1, b1_, q2, b2_,
                          scales=(s1, s2), act="gelu", impl=impl)
        return jnp.sum(y * y)

    def f_r(x_, gate_, b1_, b2_):
        y = ops.esffn_mlp(x_, ri.row_token, gate_, ri.block_expert,
                          ri.padded_counts, d1, b1_, d2, b2_,
                          act="gelu", impl=impl)
        return jnp.sum(y * y)

    args = (x, ri.row_gate, b1, b2)
    np.testing.assert_array_equal(np.asarray(f_q(*args)),
                                  np.asarray(f_r(*args)))
    gq = jax.grad(f_q, argnums=(0, 1, 2, 3))(*args)
    gr = jax.grad(f_r, argnums=(0, 1, 2, 3))(*args)
    for a, b, name in zip(gq, gr, ("dx", "dgate", "db1", "db2")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("impl", IMPLS)
def test_esffn_quantized_uneven_and_empty_experts(impl):
    """Skewed routing (one expert hoards tokens, one is empty) through the
    quantized fused op still matches the dequant reference exactly."""
    x, _, (wg, wu, wd) = _setup(seed=7)
    n, e, k, blk = x.shape[0], wg.shape[0], 2, 8
    # force expert 0 for everyone's first choice, expert 1 second; 2/3 empty
    expert_idx = jnp.stack([jnp.zeros((n,), jnp.int32),
                            jnp.ones((n,), jnp.int32)], -1)
    gates = jnp.full((n, k), 0.5, jnp.float32)
    ri = build_reindex(expert_idx, gates, e, blk)
    (qg, sg), (qu, su), (qd, sd) = (qc.quantize_blockwise(w)
                                    for w in (wg, wu, wd))
    yq = ops.esffn_glu(x, ri.row_token, ri.row_gate, ri.block_expert,
                       ri.padded_counts, qg, qu, qd, scales=(sg, su, sd),
                       impl=impl)
    yr = ops.esffn_glu(x, ri.row_token, ri.row_gate, ri.block_expert,
                       ri.padded_counts,
                       qc.dequantize_blockwise(qg, sg),
                       qc.dequantize_blockwise(qu, su),
                       qc.dequantize_blockwise(qd, sd), impl=impl)
    np.testing.assert_array_equal(np.asarray(yq), np.asarray(yr))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("transpose", [False, True])
def test_esmm_quantized_matches_reference(impl, transpose):
    x, ri, (wg, _, wd) = _setup(seed=8)
    w = wd if transpose else wg  # (E, F, D) transposed / (E, D, F) plain
    q, s = qc.quantize_blockwise(w)
    w_dq = qc.dequantize_blockwise(q, s)
    xs = jnp.asarray(np.random.default_rng(9).normal(
        size=(ri.row_token.shape[0], x.shape[1])), jnp.float32)

    def f_q(xs_):
        y = ops.esmm(xs_, q, None, ri.block_expert, ri.padded_counts,
                     w_scales=s, transpose_rhs=transpose, impl=impl)
        return jnp.sum(y * y), y

    def f_r(xs_):
        y = ops.esmm(xs_, w_dq, None, ri.block_expert, ri.padded_counts,
                     transpose_rhs=transpose, impl=impl)
        return jnp.sum(y * y), y

    (_, yq), gq = jax.value_and_grad(f_q, has_aux=True)(xs)
    (_, yr), gr = jax.value_and_grad(f_r, has_aux=True)(xs)
    np.testing.assert_array_equal(np.asarray(yq), np.asarray(yr))
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gr),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_esffn_fp8_and_int8_are_close_to_dense(mode):
    """Quantized execution approximates the ORIGINAL dense weights within
    the format's step (sanity that scales are per-block, not global)."""
    x, ri, (wg, wu, wd) = _setup(seed=10)
    qs = [qc.quantize_blockwise(w, mode=mode) for w in (wg, wu, wd)]
    yq = ops.esffn_glu(x, ri.row_token, ri.row_gate, ri.block_expert,
                       ri.padded_counts, qs[0][0], qs[1][0], qs[2][0],
                       scales=(qs[0][1], qs[1][1], qs[2][1]), impl="blocked")
    yd = ops.esffn_glu(x, ri.row_token, ri.row_gate, ri.block_expert,
                       ri.padded_counts, wg, wu, wd, impl="blocked")
    denom = np.abs(np.asarray(yd)).max() + 1e-6
    rel = np.abs(np.asarray(yq - yd)).max() / denom
    assert rel < 0.2, rel


# ---------------------------------------------------------------------------
# cost model: weight_bits terms
# ---------------------------------------------------------------------------

def test_esffn_cost_weight_bits():
    c16 = esffn_cost(256, 64, 128, 4, 2, glu=True, weight_bits=16)
    c8 = esffn_cost(256, 64, 128, 4, 2, glu=True, weight_bits=8)
    assert c8.bytes_accessed < c16.bytes_accessed
    assert c8.flops == c16.flops  # quantization changes bytes, not FLOPs
    # default (no weight_bits) equals the itemsize path
    assert esffn_cost(256, 64, 128, 4, 2, glu=True).bytes_accessed \
        == c16.bytes_accessed


def test_layer_latency_weight_bits_monotone():
    kw = dict(tokens=256, d=1024, f=4096, e=8, k=2)
    for mode in ("data_centric", "model_centric"):
        l8 = autotune.layer_latency(mode, kw["tokens"], kw["d"], kw["f"],
                                    kw["e"], kw["k"], 16, weight_bits=8)
        l16 = autotune.layer_latency(mode, kw["tokens"], kw["d"], kw["f"],
                                     kw["e"], kw["k"], 16, weight_bits=16)
        assert l8 <= l16


def test_crossover_shifts_toward_fewer_tokens_with_int8():
    """int8 experts halve the data-centric weight-movement bill, so the
    data-/model-centric crossover moves DOWN (data wins earlier) — the
    Fig. 10 roofline becoming precision-aware (DESIGN.md §8)."""
    xo16 = autotune.crossover_tokens(1024, 4096, 8, 2, n_dev=16,
                                     weight_bits=16)
    xo8 = autotune.crossover_tokens(1024, 4096, 8, 2, n_dev=16,
                                    weight_bits=8)
    assert xo16 is not None and xo8 is not None
    assert xo8 < xo16


def test_resolve_layer_mode_sees_quant():
    """A token count between the int8 and bf16 crossovers flips the
    chooser when cfg.quant is set."""
    xo16 = autotune.crossover_tokens(1024, 4096, 8, 2, n_dev=16,
                                     weight_bits=16)
    xo8 = autotune.crossover_tokens(1024, 4096, 8, 2, n_dev=16,
                                    weight_bits=8)
    tokens = (xo8 + xo16) // 2
    kw = dict(d=1024, f=4096, e=8, k=2)

    class _M:  # 16-wide TP group without a real mesh
        axis_names = ("model",)
        shape = {"model": 16}

    cfg16 = ParallelConfig(mode="auto")
    cfg8 = ParallelConfig(mode="auto", quant="int8")
    m16 = autotune.resolve_layer_mode(tokens, cfg=cfg16, mesh=_M(), **kw)
    m8 = autotune.resolve_layer_mode(tokens, cfg=cfg8, mesh=_M(), **kw)
    assert m16 == "model_centric" and m8 == "data_centric"


def test_layer_latency_uneven_per_device_bits():
    lat = (1.0, 1.0)
    all16 = autotune.layer_latency_uneven(
        "data_centric", 64, 1024, 4096, 8, 2, lat, weight_bits=16)
    all8 = autotune.layer_latency_uneven(
        "data_centric", 64, 1024, 4096, 8, 2, lat, weight_bits=[8, 8])
    assert all8 < all16
    with pytest.raises(ValueError):
        autotune.layer_latency_uneven(
            "data_centric", 64, 1024, 4096, 8, 2, lat, weight_bits=[8])


# ---------------------------------------------------------------------------
# precision-aware hetero planning / execution
# ---------------------------------------------------------------------------

def test_hetero_plan_expert_bits_validation_and_key():
    plan = make_hetero_plan((1.0, 2.0), global_batch=8, expert_bits=(8, 16))
    assert plan.expert_bits == (8, 16)
    assert plan.key() != dataclasses.replace(plan, expert_bits=None).key()
    with pytest.raises(ValueError):
        HeteroPlan(proxy_latencies=(1.0, 2.0), expert_bits=(4, 16))
    with pytest.raises(ValueError):
        HeteroPlan(proxy_latencies=(1.0, 2.0), expert_bits=(8,))


def test_hetero_exec_rejects_bits_split_mismatch():
    """expert_bits follows the data group's proxy latencies; a
    model-centric split over a different-width TP group must refuse
    rather than silently mis-map a class's precision."""
    rng = np.random.default_rng(15)
    d, f, e = 32, 512, 4
    params = {
        "router": jnp.asarray(rng.normal(size=(d, e)), jnp.float32),
        "w_gate": jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32),
    }
    plan = make_hetero_plan(
        (1.0, 2.0), global_batch=8, hidden_size=f,
        tp_latencies=(1.0, 1.0, 1.0, 2.0), expert_bits=(8, 16))
    assert len(plan.hidden_splits) == 4  # follows tp_latencies
    with pytest.raises(ValueError, match="expert_bits"):
        HeteroExecutor(params, num_experts=e, top_k=2, act="silu",
                       glu=True, blk=8, impl="blocked",
                       plan=plan, mode="model_centric")


@pytest.mark.parametrize("mode", ["data_centric", "model_centric"])
def test_hetero_exec_mixed_precision(mode):
    """Per-device-class precision (DESIGN.md §8): the int8 class holds
    measurably fewer expert-weight bytes, and its program output equals
    running the same shard against the fake-quantized (dequant∘quant)
    weights — the fused path computes the very same f32 values."""
    rng = np.random.default_rng(11)
    n, d, f, e, k = 16, 32, 256, 4, 2
    params = {
        "router": jnp.asarray(rng.normal(size=(d, e)), jnp.float32),
        "w_gate": jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    plan = make_hetero_plan((1.0, 1.0), global_batch=n, hidden_size=f,
                            expert_bits=(8, 16))
    kw = dict(num_experts=e, top_k=k, act="silu", glu=True, blk=8,
              impl="blocked", mode=mode)
    ex_q = HeteroExecutor(params, plan=plan, **kw)
    ex_d = HeteroExecutor(params, plan=dataclasses.replace(
        plan, expert_bits=None), **kw)
    bytes_q = ex_q.device_param_bytes()
    bytes_d = ex_d.device_param_bytes()
    assert bytes_q[0] < bytes_d[0]          # int8 class shrank
    assert bytes_q[1] == bytes_d[1]         # bf16 class untouched
    y_q = np.asarray(ex_q(x))
    # reference: same per-device split, weights fake-quantized where the
    # plan says 8 bits
    fq = {kk: (qc.fake_quant(v) if kk != "router" else v)
          for kk, v in params.items()}
    if mode == "data_centric":
        ex_ref0 = HeteroExecutor(fq, plan=dataclasses.replace(
            plan, expert_bits=None), **kw)
        ref0 = np.asarray(ex_ref0(x))[: plan.token_counts[0]]
        np.testing.assert_allclose(y_q[: plan.token_counts[0]], ref0,
                                   rtol=1e-6, atol=1e-6)
        # the bf16 device's shard is bit-identical to the all-dense run
        np.testing.assert_array_equal(
            y_q[plan.token_counts[0]:], np.asarray(ex_d(x))[
                plan.token_counts[0]:])
    else:
        # partial sums: quantizing one class only perturbs within the
        # int8 step of ITS hidden slice
        y_d = np.asarray(ex_d(x))
        assert not np.array_equal(y_q, y_d)
        rel = np.abs(y_q - y_d).max() / (np.abs(y_d).max() + 1e-6)
        assert rel < 0.2


# ---------------------------------------------------------------------------
# island-level integration (moe_layer / espec param dicts)
# ---------------------------------------------------------------------------

def _moe_params(rng, e, d, f, glu=True):
    p = {"router": jnp.asarray(rng.normal(size=(d, e)), jnp.float32)}
    if glu:
        p["w_gate"] = jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
        p["w_up"] = jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
        p["w_down"] = jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32)
    else:
        p["w1"] = jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
        p["b1"] = jnp.zeros((e, f), jnp.float32)
        p["w2"] = jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32)
        p["b2"] = jnp.zeros((e, d), jnp.float32)
    return p


@pytest.mark.parametrize("glu", [True, False])
def test_island_true_quant_matches_dequant_dense(glu):
    """moe_layer with quantize_ffn'd params (int8 payloads + scale leaves)
    equals moe_layer on the hand-dequantized dense weights."""
    from jax.sharding import PartitionSpec as P

    rng = np.random.default_rng(12)
    b, s, d, f, e, k = 2, 8, 32, 48, 4, 2
    p = _moe_params(rng, e, d, f, glu=glu)
    x = jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
    ms = MoEStatic(num_experts=e, top_k=k, act="silu" if glu else "gelu",
                   glu=glu)
    cfg = ParallelConfig(blk=8, impl="blocked")
    qp = qc.quantize_ffn(p)
    dq = dict(p)
    for name in qc.EXPERT_WEIGHT_KEYS:
        if name in qp and f"{name}_scale" in qp:
            dq[name] = qc.dequantize_blockwise(qp[name], qp[f"{name}_scale"])

    def as_mp(src):
        return MoEParams(**{fld: src.get(fld)
                            for fld in MoEParams._fields
                            if src.get(fld) is not None or fld == "router"})

    y_q, aux_q, _ = moe_layer(x, as_mp(qp), ms, cfg, None,
                              x_spec=P(None, None, None))
    y_d, aux_d, _ = moe_layer(x, as_mp(dq), ms, cfg, None,
                              x_spec=P(None, None, None))
    np.testing.assert_array_equal(np.asarray(y_q), np.asarray(y_d))
    np.testing.assert_array_equal(np.asarray(aux_q), np.asarray(aux_d))


def test_island_qat_fake_quant_and_router_grads():
    """cfg.quant='int8' runs the STE fake-quant inside the island: outputs
    equal espec on hand-fake-quantized weights, weight/router grads flow
    (STE), and the router grad is computed at full precision (identical to
    the unquantized router-grad path given the same FFN output values)."""
    from jax.sharding import PartitionSpec as P

    rng = np.random.default_rng(13)
    b, s, d, f, e, k = 2, 8, 32, 48, 4, 2
    p = _moe_params(rng, e, d, f, glu=True)
    x = jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
    ms = MoEStatic(num_experts=e, top_k=k, act="silu", glu=True)
    cfg_q = ParallelConfig(blk=8, impl="blocked", quant="int8")
    cfg_d = ParallelConfig(blk=8, impl="blocked")

    def as_mp(src):
        return MoEParams(**{fld: src.get(fld)
                            for fld in MoEParams._fields
                            if src.get(fld) is not None or fld == "router"})

    def loss(params, cfg):
        y, aux, z = moe_layer(x, as_mp(params), ms, cfg, None,
                              x_spec=P(None, None, None))
        return jnp.sum(y * y) + aux

    fq = {kk: (qc.fake_quant(v, "int8", cfg_q.quant_tile)
               if kk != "router" else v) for kk, v in p.items()}
    np.testing.assert_array_equal(
        np.asarray(loss(p, cfg_q)), np.asarray(loss(fq, cfg_d)))
    g = jax.grad(loss)(p, cfg_q)
    for name, gv in g.items():
        assert np.isfinite(np.asarray(gv)).all(), name
        assert np.abs(np.asarray(gv)).max() > 0, name


def test_island_rejects_quantized_with_tp_mesh():
    """True-quantized experts need whole-expert layouts: a TP'd island
    must refuse rather than silently mis-scale."""
    pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(14)
    d, f, e = 32, 64, 4
    p = qc.quantize_ffn(_moe_params(rng, e, d, f))
    x = jnp.asarray(rng.normal(size=(2, 8, d)), jnp.float32)
    ms = MoEStatic(num_experts=e, top_k=2)
    mesh = make_mesh((2,), ("model",))
    cfg = ParallelConfig(mode="model_centric", blk=8, impl="blocked")
    mp = MoEParams(**{fld: p.get(fld) for fld in MoEParams._fields
                      if p.get(fld) is not None or fld == "router"})
    with pytest.raises(NotImplementedError):
        moe_layer(x, mp, ms, cfg, mesh, x_spec=P(None, None, None))


def test_quantize_lm_params_walker():
    """Only MoE expert weights quantize; router/attention/embed/dense
    stay; total bytes shrink."""
    import dataclasses as dc

    from repro import configs as cfglib
    from repro.common import tree_bytes
    from repro.models import lm
    from repro.parallel.sharding import split_tree

    cfg = dc.replace(cfglib.get_smoke_config("qwen3-moe-30b-a3b"),
                     dtype="float32")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    qp = qc.quantize_lm_params(params, cfg, mode="int8")
    assert tree_bytes(qp) < tree_bytes(params)
    moe_pos = [i for i in range(cfg.period) if cfg.is_moe_layer(i)]
    for pos in moe_pos:
        ffn = qp["layers"][pos]["ffn"]
        assert ffn["w_gate"].dtype == jnp.int8
        assert ffn["w_gate_scale"].dtype == jnp.float32
        assert ffn["router"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(qp["embed"]), np.asarray(params["embed"]))
