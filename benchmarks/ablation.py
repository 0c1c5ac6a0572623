"""Paper Figure 12 — ablations: pipeline-shared cache, fused backward
kernel, memory-latency trade-off.

  * cache (janus vs shared_cache): peak memory of a train step on an
    8-fake-device mesh (subprocess via the dryrun harness on the smoke
    config) — Janus retains gathered expert params for backward, the
    shared cache re-gathers.
  * fused kernel (ESFK vs ESTMM+ESS): wall time of the MoE backward.
  * memopt (scatter-add combine vs per-choice materialisation): peak
    memory of the MoE FFN fwd+bwd.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import compiled_stats, emit, time_fn
from repro.core import espec
from repro.core.reindex import build_reindex, combine_scatter, gather_sorted
from repro.core.routing import route
from repro.kernels import ops

N, D, F, E, K, BLK = 512, 128, 256, 8, 4, 32


def _setup(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (N, D))
    p = {
        "router": jax.random.normal(ks[1], (D, E)) * 0.2,
        "w1": jax.random.normal(ks[2], (E, D, F)) * 0.2,
        "b1": jnp.zeros((E, F)),
        "w2": jax.random.normal(ks[3], (E, F, D)) * 0.2,
        "b2": jnp.zeros((E, D)),
    }
    return x, p


def bench_fused_kernel():
    x, p = _setup()

    def loss(p, fused):
        out = espec.hexa_moe_ffn(
            x, p, num_experts=E, top_k=K, act="gelu", glu=False, blk=BLK,
            impl="pallas",
        )
        return jnp.sum(out.y ** 2)

    for fused in (True, False):
        ops.set_fused_backward(fused)
        g = jax.jit(jax.grad(lambda p: loss(p, fused)))
        us = time_fn(g, p, iters=3, warmup=1)
        emit(f"ablation_F12/fused_kernel/{'esfk' if fused else 'unfused'}",
             us, "pallas interpret on CPU")
    ops.set_fused_backward(True)


def bench_memopt():
    x, p = _setup()

    def loss_memopt(p):
        out = espec.hexa_moe_ffn(
            x, p, num_experts=E, top_k=K, act="gelu", glu=False, blk=BLK,
            impl="blocked",
        )
        return jnp.sum(out.y ** 2)

    def loss_naive(p):
        # paper Fig. 5(a): one full ESMM pipeline per routing choice,
        # materialising k per-choice outputs before summation.
        r = route(x, p["router"], K)
        total = 0.0
        outs = []
        for s in range(K):
            ri = build_reindex(
                r.expert_idx[:, s:s + 1], r.gates[:, s:s + 1], E, BLK
            )
            xs = gather_sorted(x, ri)
            h = ops.esmm(xs, p["w1"], p["b1"], ri.block_expert,
                         ri.padded_counts, impl="blocked")
            h = jax.nn.gelu(h)
            ys = ops.esmm(h, p["w2"], p["b2"], ri.block_expert,
                          ri.padded_counts, impl="blocked")
            outs.append(combine_scatter(ys, ri, N))
        y = sum(outs)
        return jnp.sum(y ** 2)

    for name, fn in (("memopt", loss_memopt), ("naive_topk", loss_naive)):
        stats = compiled_stats(jax.grad(fn), p)
        emit(f"ablation_F12/memopt/{name}", 0.0,
             f"peak_MB={stats['peak_bytes'] / 1e6:.1f};"
             f"flops={stats['flops']:.3e}")


def bench_cache_policy():
    """shared_cache vs janus peak memory on an 8-device mesh (subprocess)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.launch import inputs as inputs_lib, steps as steps_lib
from repro.launch.mesh import make_mesh
from repro.configs.base import ShapeConfig
from repro.optim import adamw
from repro.parallel.sharding import ParallelConfig
import dataclasses

cfg = get_smoke_config("mixtral-8x7b")
cfg = dataclasses.replace(cfg, num_layers=4, d_model=256, vocab_size=512,
                          moe=dataclasses.replace(cfg.moe, d_ff=512))
shape = ShapeConfig("bench", "train", 512, 8)
mesh = make_mesh((2, 4), ("data", "model"))
out = {}
for policy in ("shared_cache", "janus", "none"):
    pcfg = ParallelConfig(mode="data_centric", cache_policy=policy,
                          remat="none" if policy == "none" else "block",
                          blk=32, impl="blocked", scan_layers=False)
    opt_cfg = adamw.OptimizerConfig(master_fp32=False)
    ap, _, _ = steps_lib.sharded_params(cfg, pcfg, mesh)
    batch = inputs_lib.input_specs(cfg, shape, pcfg, mesh)
    opt = steps_lib.sharded_opt_state(ap, opt_cfg, mesh)
    sf = steps_lib.make_train_step(cfg, pcfg, mesh, opt_cfg,
                                   (shape.global_batch, shape.seq_len, cfg.d_model))
    with mesh:
        c = jax.jit(sf).lower(ap, opt, batch).compile()
    ma = c.memory_analysis()
    out[policy] = ma.argument_size_in_bytes + ma.temp_size_in_bytes
print("RESULT" + json.dumps(out))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # The child's 8 host devices are the CPU backend's; a chip, if any,
    # belongs to the parent process.
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT")]
    if not line:
        raise RuntimeError(
            "cache-policy child printed no RESULT line "
            f"(exit {res.returncode}):\n{res.stderr[-3000:]}")
    out = json.loads(line[0][len("RESULT"):])
    for policy, peak in out.items():
        emit(f"ablation_F12/cache_policy/{policy}", 0.0,
             f"peak_MB={peak / 1e6:.1f}")


def run(quick: bool = True):
    bench_fused_kernel()
    bench_memopt()
    bench_cache_policy()


if __name__ == "__main__":
    run()
