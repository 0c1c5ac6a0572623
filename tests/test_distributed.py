"""Distributed-semantics tests, run in subprocesses with 8 fake devices
(the main test process keeps the 1-device contract)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.multihost  # subprocess fake-device mesh tier

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout: int = 900) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # Force the CPU backend: with JAX_PLATFORMS unset, jax probes the TPU
    # plugin first, and off-TPU that means minutes of GCP-metadata retries
    # before the CPU fallback. Fake devices come from XLA_FLAGS regardless.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT")]
    assert line, res.stdout[-2000:]
    return json.loads(line[-1][len("RESULT"):])


PREAMBLE = r"""
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.moe_parallel import MoEParams, MoEStatic, moe_layer
from repro.parallel.sharding import ParallelConfig
from repro.core import espec

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
B, S, D, F, E, K = 8, 16, 32, 64, 4, 2
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
    num_experts=E, top_k=K, act="silu", glu=True, blk=16).y.reshape(B, S, D)
"""


def test_all_modes_match_oracle():
    out = run_sub(PREAMBLE + r"""
errs = {}
for mode in ("hybrid", "model_centric", "data_centric", "ep"):
    for sched in ("ag_rs", "ag_ar"):
        cfg = ParallelConfig(mode=mode, collective_schedule=sched, blk=16,
                             capacity_factor=8.0)  # EP: no drops
        spec = P("data", "model", None)
        with mesh:
            y, aux, z = jax.jit(
                lambda x, p: moe_layer(x, p, ms, cfg, mesh, x_spec=spec)
            )(x, p)
        errs[f"{mode}/{sched}"] = float(jnp.abs(y - ref).max())
print("RESULT" + json.dumps(errs))
""")
    for key, err in out.items():
        assert err < 5e-5, (key, err)


def test_grads_match_across_modes():
    out = run_sub(PREAMBLE + r"""
def loss(p, mode):
    cfg = ParallelConfig(mode=mode, blk=16)
    spec = P("data", "model", None)
    y, aux, z = moe_layer(x, p, ms, cfg, mesh, x_spec=spec)
    return jnp.sum(y ** 2)

with mesh:
    g_h = jax.jit(jax.grad(lambda p: loss(p, "hybrid")))(p)
    g_m = jax.jit(jax.grad(lambda p: loss(p, "model_centric")))(p)
    g_d = jax.jit(jax.grad(lambda p: loss(p, "data_centric")))(p)
errs = {}
for name, g in (("model", g_m), ("data", g_d)):
    errs[name] = max(
        float(jnp.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(g_h), jax.tree.leaves(g))
    )
print("RESULT" + json.dumps(errs))
""")
    for key, err in out.items():
        assert err < 1e-3, (key, err)


def test_sharded_train_step_runs_and_matches_single_device():
    out = run_sub(r"""
import json, dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_smoke_config
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.optim import adamw
from repro.parallel.sharding import ParallelConfig, split_tree, tree_shardings

cfg = dataclasses.replace(get_smoke_config("mixtral-8x7b"), dtype="float32")
B, S = 8, 32
rng = np.random.default_rng(0)
toks = rng.integers(0, cfg.vocab_size, size=(B, S)).astype(np.int32)
batch = {"tokens": jnp.asarray(toks),
         "labels": jnp.asarray(np.roll(toks, -1, 1)),
         "loss_mask": jnp.ones((B, S), jnp.float32)}
opt_cfg = adamw.OptimizerConfig(master_fp32=False)

def run(mesh):
    pcfg = ParallelConfig(blk=8)
    params, specs = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    if mesh is not None:
        params = jax.tree.map(jax.device_put, params,
                              tree_shardings(params, specs, pcfg, mesh))
    opt = adamw.init_opt_state(params, opt_cfg)
    # Moments live where their params live, not whole on one device.
    placed = all(m.sharding == p.sharding for m, p in zip(
        jax.tree.leaves(opt["m"]), jax.tree.leaves(params)))
    step = jax.jit(steps_lib.make_train_step(cfg, pcfg, mesh, opt_cfg,
                                             (B, S, cfg.d_model)))
    losses = []
    for i in range(3):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    return losses, placed

mesh = make_mesh((4, 2), ("data", "model"))
with mesh:
    dist, placed = run(mesh)
single, _ = run(None)
print("RESULT" + json.dumps({"dist": dist, "single": single,
                             "placed": placed}))
""")
    assert out["placed"], "optimizer moments not sharded like their params"
    for a, b in zip(out["dist"], out["single"]):
        assert abs(a - b) < 2e-3, (out["dist"], out["single"])


def test_compressed_psum_matches_exact():
    out = run_sub(r"""
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.optim.compression import compressed_psum
from repro.launch.mesh import make_mesh
from repro.parallel.moe_parallel import _shard_map

mesh = make_mesh((8,), ("pod",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

def body(g_loc):
    out, res = compressed_psum(g_loc[0], "pod", jnp.zeros_like(g_loc[0]))
    return out[None], res[None]

with mesh:
    out, res = jax.jit(_shard_map(
        body, mesh, in_specs=(P("pod", None),),
        out_specs=(P("pod", None), P("pod", None)),
    ))(g)
exact = jnp.sum(g, axis=0)
rel = float(jnp.linalg.norm(out[0] - exact) / jnp.linalg.norm(exact))
resid_ok = bool(jnp.isfinite(res).all())
print("RESULT" + json.dumps({"rel": rel, "resid_ok": resid_ok}))
""")
    assert out["rel"] < 0.02, out
    assert out["resid_ok"]


def test_elastic_restore_onto_different_mesh(tmp_path):
    out = run_sub(r"""
import json, dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.checkpoint import manager as ckpt
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.parallel.sharding import ParallelConfig, split_tree, tree_shardings

cfg = dataclasses.replace(get_smoke_config("phi3-medium-14b"), dtype="float32")
pcfg = ParallelConfig(blk=8)
params, specs = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))

mesh_a = make_mesh((4, 2), ("data", "model"))
pa = jax.tree.map(jax.device_put, params,
                  tree_shardings(params, specs, pcfg, mesh_a))
import tempfile, os
d = tempfile.mkdtemp()
ckpt.save(d, 1, pa, meta={"step": 1})

# "job restarted with fewer devices": new 2x2 mesh over first 4 devices
from jax.sharding import Mesh
import numpy as onp
mesh_b = Mesh(onp.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
sh_b = tree_shardings(params, specs, pcfg, mesh_b)
pb, _ = ckpt.restore(d, 1, params, sh_b)
ok = all(
    bool(np.allclose(np.asarray(a), np.asarray(b)))
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb))
)
devs = {json.dumps(dict(x.sharding.mesh.shape)) for x in jax.tree.leaves(pb)}
print("RESULT" + json.dumps({"ok": ok, "meshes": sorted(devs)}))
""")
    assert out["ok"]
    assert json.loads(out["meshes"][0]) == {"data": 2, "model": 2}
