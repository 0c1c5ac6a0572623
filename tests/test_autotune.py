"""Adaptive centric dispatch (paper §4.5 / Fig. 10) + pipeline-shared cache.

Covers the ISSUE-1 acceptance criteria:
  (a) the runtime chooser flips model->data centric at the workload the
      Fig. 10 roofline sweep predicts (same grid, same cost model object),
  (b) mode="auto" produces bitwise-identical outputs to the forced layer
      mode — single-process AND on an 8-device mesh (subprocess),
  (c) the pipeline-shared cache never holds more than its configured number
      of layers' gathered params, while prefetch keeps the next layer warm.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import lm
from repro.parallel import autotune
from repro.parallel.cache import (
    PipelineSharedCache,
    gather_ffn_params,
    gathered_layer_bytes,
    tree_bytes,
)
from repro.parallel.sharding import ParallelConfig, split_tree

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

D, F, E, K = 1024, 4096, 8, 2  # the Fig. 10 layer


# ------------------------------------------------------------- (a) roofline

def test_choose_mode_matches_roofline_crossover():
    """The chooser's flip point == the Fig. 10 sweep's flip point."""
    grid = [2 ** i for i in range(4, 18)]
    sweep_winner = [
        "model_centric"
        if autotune.layer_latency("model_centric", t, D, F, E, K, 16)
        < autotune.layer_latency("data_centric", t, D, F, E, K, 16)
        else "data_centric"
        for t in grid
    ]
    flips = [grid[i] for i in range(1, len(grid))
             if sweep_winner[i] != sweep_winner[i - 1]]
    assert len(flips) == 1, "roofline must cross exactly once on this grid"
    crossover = flips[0]
    assert autotune.crossover_tokens(D, F, E, K, n_dev=16) == crossover
    for t in grid:
        expect = "model_centric" if t < crossover else "data_centric"
        assert autotune.choose_mode(t, D, F, E, K, n_dev=16) == expect


def test_small_workload_prefers_model_centric_large_prefers_data():
    assert autotune.choose_mode(64, D, F, E, K, n_dev=16) == "model_centric"
    assert autotune.choose_mode(2 ** 17, D, F, E, K, n_dev=16) == "data_centric"


def test_effective_devices_heterogeneity():
    # homogeneous group: full size; half-speed straggler: counts as 0.5
    assert autotune.effective_devices([1.0, 1.0, 1.0, 1.0]) == 4.0
    assert autotune.effective_devices([1.0, 2.0]) == pytest.approx(1.5)
    with pytest.raises(ValueError):
        autotune.effective_devices([1.0, -1.0])


class _StubMesh:
    """Static mesh stand-in (axes()/resolve_layer_mode only read names and
    extents, never devices)."""
    axis_names = ("data", "model")
    shape = {"data": 2, "model": 8}


def test_hetero_latencies_shift_the_decision():
    """Straggler-degraded TP group: the effective device count shrinks, the
    group turns compute-bound at the crossover workload, and the tie-break
    keeps model-centric (no weight movement) where the healthy group had
    already switched to data-centric."""
    t = autotune.crossover_tokens(D, F, E, K, n_dev=8)
    healthy = ParallelConfig(mode="auto")
    degraded = ParallelConfig(mode="auto",
                              device_latencies=tuple([1.0] + [7.0] * 7))
    n_eff = autotune.effective_devices(degraded.device_latencies)
    assert n_eff == pytest.approx(2.0)
    kw = dict(d=D, f=F, e=E, k=K, mesh=_StubMesh(), layer_idx=0)
    assert autotune.resolve_layer_mode(t, cfg=healthy, **kw) == "data_centric"
    assert autotune.resolve_layer_mode(t, cfg=degraded, **kw) == "model_centric"


def test_plan_layer_modes_per_period_position():
    cfg = ModelConfig(
        name="t", family="moe", num_layers=4, d_model=D, num_heads=8,
        num_kv_heads=8, d_ff=D * 4, vocab_size=64,
        moe=MoEConfig(num_experts=E, top_k=K, d_ff=F, period=2, offset=1),
    )
    pcfg = ParallelConfig(mode="auto")
    plan = autotune.plan_layer_modes(cfg, pcfg, None, tokens=64)
    assert len(plan) == cfg.period
    assert plan[0] is None                  # dense position
    assert plan[1] in ("model_centric", "data_centric")
    # pinning the plan into the config overrides the chooser
    pinned = ParallelConfig(mode="auto", layer_mode_plan=plan)
    got = autotune.resolve_layer_mode(
        10 ** 9, d=D, f=F, e=E, k=K, cfg=pinned, mesh=None, layer_idx=1)
    assert got == plan[1]


# ------------------------------------------------- (b) auto == forced, exact

def _tiny_cfg():
    return ModelConfig(
        name="tiny-moe", family="moe", num_layers=4, d_model=32,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=0, vocab_size=64,
        moe=MoEConfig(num_experts=4, top_k=2, d_ff=48),
    )


def _fwd(cfg, params, toks, pcfg, mode="train"):
    logits, _, aux, z = lm.forward(
        params, {"tokens": toks}, cfg, pcfg, None, mode=mode)
    return np.asarray(logits)


def test_auto_bitwise_equals_forced_single_process():
    cfg = _tiny_cfg()
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    auto = _fwd(cfg, params, toks, ParallelConfig(mode="auto", blk=16))
    for forced in ("data_centric", "model_centric"):
        got = _fwd(cfg, params, toks, ParallelConfig(
            mode="auto", blk=16, forced_layer_mode=forced))
        assert np.array_equal(auto, got), forced


def test_unrolled_cache_path_bitwise_equals_uncached():
    """The prefetch cache is an inference-side mechanism (prefill/decode);
    under the remat'd train step the remat policy is the cache instead."""
    cfg = _tiny_cfg()
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    base = _fwd(cfg, params, toks, ParallelConfig(
        mode="auto", blk=16, scan_layers=False, cache_layers=0),
        mode="prefill")
    cached = _fwd(cfg, params, toks, ParallelConfig(
        mode="auto", blk=16, scan_layers=False, cache_layers=2),
        mode="prefill")
    assert np.array_equal(base, cached)
    st = lm.LAST_PIPELINE_CACHE_STATS
    assert st is not None
    assert st["peak_resident_layers"] <= 2
    assert st["hits"] > 0  # prefetch made every later fetch a hit
    # overlap_dispatch (DESIGN.md §10): prefetching the MoE positions'
    # expert collectives alongside the fsdp gathers must stay bitwise
    # equal with the same residency bound.
    overlap = _fwd(cfg, params, toks, ParallelConfig(
        mode="auto", blk=16, scan_layers=False, cache_layers=2,
        overlap_dispatch=True),
        mode="prefill")
    assert np.array_equal(base, overlap)
    st = lm.LAST_PIPELINE_CACHE_STATS
    assert st is not None and st["peak_resident_layers"] <= 2


def test_cache_skipped_under_remat_train_and_rejected_with_scan():
    """Train mode with remat active must NOT route gathered params through
    the checkpointed period_fn (they would be saved as residuals — Janus
    residency); and cache_layers>0 with scan_layers=True is a config error,
    not a silent no-op."""
    cfg = _tiny_cfg()
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    lm.LAST_PIPELINE_CACHE_STATS = None
    _fwd(cfg, params, toks, ParallelConfig(
        mode="auto", blk=16, scan_layers=False, cache_layers=2),
        mode="train")
    assert lm.LAST_PIPELINE_CACHE_STATS is None  # prefetcher skipped
    with pytest.raises(ValueError, match="scan_layers"):
        _fwd(cfg, params, toks, ParallelConfig(
            mode="auto", blk=16, scan_layers=True, cache_layers=2),
            mode="prefill")


@pytest.mark.multihost
def test_auto_mode_on_mesh_bitwise_equals_forced():
    """8 fake CPU devices (subprocess, same idiom as test_distributed):
    mode="auto" on a (4,2) mesh must equal the forced layer mode bitwise and
    the single-device oracle numerically — for a workload on each side of
    the crossover (decode-sized vs prefill-sized)."""
    code = r"""
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import espec
from repro.parallel import autotune
from repro.parallel.moe_parallel import MoEParams, MoEStatic, moe_layer
from repro.parallel.sharding import ParallelConfig

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
D, F, E, K = 32, 64, 4, 2
out = {}
for B, S in ((8, 16), (8, 512)):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (B, S, D), jnp.float32)
    p = MoEParams(router=jax.random.normal(ks[1], (D, E)) * 0.1,
                  w_gate=jax.random.normal(ks[2], (E, D, F)) * 0.1,
                  w_up=jax.random.normal(ks[3], (E, D, F)) * 0.1,
                  w_down=jax.random.normal(ks[4], (E, F, D)) * 0.1)
    ms = MoEStatic(num_experts=E, top_k=K, act="silu", glu=True)
    ref = espec.hexa_moe_ffn(
        x.reshape(B * S, D),
        {"router": p.router, "w_gate": p.w_gate, "w_up": p.w_up,
         "w_down": p.w_down},
        num_experts=E, top_k=K, act="silu", glu=True, blk=16).y
    ref = ref.reshape(B, S, D)
    spec = P("data", "model", None)
    chosen = autotune.choose_mode(B * S // 4, D, F, E, K, n_dev=2)
    def run(cfg):
        with mesh:
            y, aux, z = jax.jit(
                lambda x, p: moe_layer(x, p, ms, cfg, mesh, x_spec=spec)
            )(x, p)
        return np.asarray(y)
    y_auto = run(ParallelConfig(mode="auto", blk=16))
    y_forced = run(ParallelConfig(mode="auto", blk=16,
                                  forced_layer_mode=chosen))
    y_other = run(ParallelConfig(
        mode="auto", blk=16,
        forced_layer_mode=("data_centric" if chosen == "model_centric"
                           else "model_centric")))
    out[f"{B}x{S}"] = {
        "chosen": chosen,
        "bitwise_forced": bool(np.array_equal(y_auto, y_forced)),
        "err_auto": float(np.abs(y_auto - ref).max()),
        "err_other": float(np.abs(y_other - ref).max()),
    }
print("RESULT" + json.dumps(out))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # Force CPU: with JAX_PLATFORMS unset, jax probes the TPU plugin and
    # off-TPU that stalls for minutes in GCP-metadata retries (see
    # test_distributed.run_sub). Fake devices come from XLA_FLAGS.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT")]
    assert line, res.stdout[-2000:]
    out = json.loads(line[-1][len("RESULT"):])
    modes = {cell["chosen"] for cell in out.values()}
    for key, cell in out.items():
        assert cell["bitwise_forced"], (key, cell)
        assert cell["err_auto"] < 5e-5, (key, cell)
        assert cell["err_other"] < 5e-5, (key, cell)
    # both dispatches exercised: small workload -> model, large -> data
    assert out["8x16"]["chosen"] == "model_centric"
    assert out["8x512"]["chosen"] == "data_centric"
    assert modes == {"model_centric", "data_centric"}


# --------------------------------------------------- (c) cache residency

def test_cache_never_exceeds_capacity():
    gathers = []
    layer = {"w": jnp.zeros((4, 8, 16), jnp.bfloat16)}

    def gather(l):
        gathers.append(l)
        return layer

    cache = PipelineSharedCache(2)
    for l in range(10):
        cache.fetch(l, lambda l=l: gather(l))
        assert cache.resident_layers <= 2
        if l + 1 < 10:
            cache.prefetch(l + 1, lambda l=l: gather(l + 1))
            assert cache.resident_layers <= 2
    st = cache.stats()
    assert st["peak_resident_layers"] == 2
    assert st["misses"] == 1                    # only layer 0 stalls...
    assert st["prefetches"] == 9                # ...the rest gather ahead
    assert st["hits"] == 9                      # and hit at fetch time
    assert st["evictions"] == 8
    assert gathers == list(range(10))
    assert st["peak_resident_bytes"] == 2 * tree_bytes(layer)


def test_cache_capacity_one_and_validation():
    cache = PipelineSharedCache(1)
    for l in range(5):
        cache.fetch(l, lambda: {"w": jnp.zeros((2, 2))})
        assert cache.resident_layers == 1
    assert cache.stats()["peak_resident_layers"] == 1
    with pytest.raises(ValueError):
        PipelineSharedCache(0)


def test_evicted_layer_regathers():
    calls = {"n": 0}

    def gather():
        calls["n"] += 1
        return {"w": jnp.zeros((2, 2))}

    cache = PipelineSharedCache(1)
    cache.fetch("a", gather)
    cache.fetch("b", gather)   # evicts a
    cache.fetch("a", gather)   # must re-gather
    assert calls["n"] == 3
    cache.fetch("a", gather)   # resident -> hit
    assert calls["n"] == 3


def test_gather_ffn_params_no_mesh_is_identity():
    ffn = {
        "router": jnp.zeros((8, 4)),
        "w_gate": jnp.zeros((4, 8, 16)),
        "w_up": jnp.zeros((4, 8, 16)),
        "w_down": jnp.zeros((4, 16, 8)),
    }
    out = gather_ffn_params(ffn, ParallelConfig(mode="auto"), None)
    assert set(out) == set(ffn)
    for key in ffn:
        assert out[key] is ffn[key]


def test_gathered_layer_bytes():
    assert gathered_layer_bytes(D, F, E, glu=True) == E * 3 * D * F * 2
    mlp = gathered_layer_bytes(D, F, E, glu=False)
    assert mlp == E * 2 * D * F * 2 + E * (F + D) * 4


def test_forward_cache_bound_two_moe_positions_per_period():
    """Regression: with >1 MoE layer per period, one cache entry is the
    whole period — the residency bound counts what is actually live."""
    cfg = ModelConfig(
        name="per2", family="moe", num_layers=4, d_model=32,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=0, vocab_size=64,
        attn_pattern=("global", "local"), window=8,
        moe=MoEConfig(num_experts=4, top_k=2, d_ff=48),
    )
    assert cfg.period == 2
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    base = _fwd(cfg, params, toks, ParallelConfig(
        mode="auto", blk=16, scan_layers=False, cache_layers=0),
        mode="prefill")
    cached = _fwd(cfg, params, toks, ParallelConfig(
        mode="auto", blk=16, scan_layers=False, cache_layers=2),
        mode="prefill")
    assert np.array_equal(base, cached)
    st = lm.LAST_PIPELINE_CACHE_STATS
    assert st["peak_resident_layers"] <= 2   # 2 periods = all 4 MoE layers
    assert st["misses"] == 1                 # period 0 stalls
    assert st["prefetches"] == 1             # period 1 gathers ahead
    assert st["hits"] == 1                   # and hits at fetch time


def test_forward_cache_bound_deep_model():
    """Through the real forward: an 8-layer MoE LM, cache capacity 2 —
    peak gathered residency stays 2 while all 8 layers are gathered."""
    cfg = ModelConfig(
        name="deep", family="moe", num_layers=8, d_model=32,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=0, vocab_size=64,
        moe=MoEConfig(num_experts=4, top_k=2, d_ff=48),
    )
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    _fwd(cfg, params, toks, ParallelConfig(
        mode="auto", blk=16, scan_layers=False, cache_layers=2),
        mode="prefill")
    st = lm.LAST_PIPELINE_CACHE_STATS
    assert st["peak_resident_layers"] == 2
    assert st["misses"] == 1      # only period 0 on the critical path
    assert st["prefetches"] == 7  # periods 1-7 gathered ahead of use
    assert st["evictions"] == 6
