#!/usr/bin/env python3
"""Smoke run of the train and serve paths on a TPU. Not a benchmark.

    python chip_smoke.py               # one chip: Swin-MoE train + paged serve
    python chip_smoke.py --four-chips  # four chips: sharded MoE training only

One chip runs two phases through the repo's own entry points:

  train  Swin-MoE-Small as published (224^2, depths 2/2/18/2, 8 experts
         top-1), ``moe_impl="hexa"`` on the Pallas kernels, batch 32, five
         AdamW steps (``launch.steps.make_swin_train_step``). Losses
         must be finite. Against the XLA ``blocked`` implementation on the
         same chip, the first loss must agree within ``TRAIN_RTOL`` and the
         gradient of every MoE-block parameter within ``TRAIN_GRAD_REL_L2``.
  serve  qwen3-moe-30b-a3b at published widths cut to 4 layers, 8 requests
         x 16 new tokens through ``launch.serve.PagedServer``. Against
         ``blocked``, the prefill logits and one decode step's logits, with
         bfloat16 and with int8 KV pages, must agree within ``SERVE_REL_L2``.

``--four-chips`` runs only the sharded expert path of paper §4.3:
qwen3-moe-30b-a3b cut to one layer, three ``make_train_step`` steps on a
2x2 mesh under ``data_centric`` and then ``model_centric`` from the same
weights and batch. Their losses must agree within ``CENTRIC_RTOL``, and the
expert weights must sit on all four chips.

Every compiled step must contain Mosaic kernels (``tpu_custom_call``):
the Pallas kernels ran, not their interpreter. Every ``blocked`` reference
must contain none. Weights and data are random,
made from ``--seed``. Times printed are those of one short run, labelled
``smoke``. Any failure exits non-zero; on success the last line of stdout
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common import cdiv, enable_compile_cache  # noqa: E402

#: Text that marks a Mosaic kernel in a compiled TPU program.
KERNEL_MARK = "tpu_custom_call"
#: First-step loss, Pallas vs blocked, float32 Swin: relative tolerance.
TRAIN_RTOL = 1e-2
#: First-step gradient of each MoE-block parameter, Pallas vs blocked,
#: float32 Swin: relative L2 error.
TRAIN_GRAD_REL_L2 = 5e-2
#: Prefill and decode logits, Pallas vs blocked, bfloat16 qwen3: relative
#: L2 error.
SERVE_REL_L2 = 5e-2
#: Per-step loss, data- vs model-centric, bfloat16 qwen3: relative.
CENTRIC_RTOL = 1e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def compile_checked(name: str, fn, *args, kernels: bool = True,
                    donate: tuple = ()):
    """AOT-compile ``fn`` for ``args``. With ``kernels`` the program must
    hold a Mosaic kernel; without, it must hold none (an XLA reference)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    secs = time.perf_counter() - t0
    if (KERNEL_MARK in compiled.as_text()) != kernels:
        raise AssertionError(
            f"{name}: compiled step {'lacks' if kernels else 'holds'} "
            f"{KERNEL_MARK}")
    log(f"{name}: compiled in {secs:.1f}s, Mosaic kernels "
        + ("present" if kernels else "absent"))
    return compiled


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), np.finfo(np.float64).tiny))


def moe_leaves(tree, path: str = ""):
    """(path, leaf) for every parameter of every MoE block: the dicts of
    the tree that hold a ``router``."""
    if isinstance(tree, dict):
        if "router" in tree:
            for key, leaf in sorted(tree.items()):
                yield f"{path}/{key}", leaf
            return
        for key, sub in sorted(tree.items()):
            yield from moe_leaves(sub, f"{path}/{key}")
    elif isinstance(tree, (list, tuple)):
        for i, sub in enumerate(tree):
            yield from moe_leaves(sub, f"{path}/{i}")


def moe_grad_errors(got: dict, want: dict) -> dict:
    """Relative L2 error of each MoE-block gradient leaf against the
    reference's; both are ``dict(moe_leaves(grads))``."""
    errors = {path: rel_l2(leaf, want[path]) for path, leaf in got.items()}
    if not errors:
        raise AssertionError("no MoE block in the gradient tree")
    return errors


def bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def train_phase(cfg, *, batch: int, steps: int, seed: int) -> None:
    from repro.launch import steps as steps_lib
    from repro.models import swin
    from repro.optim import adamw
    from repro.parallel.sharding import ParallelConfig, split_tree

    opt_cfg = adamw.OptimizerConfig(master_fp32=False)
    train = steps_lib.make_swin_train_step(
        cfg, ParallelConfig(impl="pallas"), opt_cfg)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(lambda k: split_tree(swin.init_swin(k, cfg))[0])(key)
    opt = adamw.init_opt_state(params, opt_cfg)
    kx, ky = jax.random.split(jax.random.fold_in(key, 1))
    images = jax.random.normal(
        kx, (batch, cfg.img_size, cfg.img_size, cfg.in_chans), jnp.float32)
    labels = jax.random.randint(ky, (batch,), 0, cfg.num_classes)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"train: {cfg.name}, {n_params / 1e6:.1f}M params, batch {batch}")

    step = compile_checked("train step (pallas)", train,
                           params, opt, images, labels)
    # The first step's loss and gradients on both paths, from one set of
    # weights; only the MoE blocks' gradients are kept.
    grads = {}
    for impl in ("pallas", "blocked"):
        loss_fn = steps_lib.make_swin_loss_fn(cfg, ParallelConfig(impl=impl))

        def loss_and_moe_grads(p, x, y, loss_fn=loss_fn):
            loss, g = jax.value_and_grad(loss_fn)(p, x, y)
            return loss, dict(moe_leaves(g))

        fn = compile_checked(f"loss and gradients ({impl})",
                             loss_and_moe_grads, params, images, labels,
                             kernels=impl == "pallas")
        grads[impl] = jax.device_get(fn(params, images, labels))
    ref_loss = float(grads["blocked"][0])
    errors = moe_grad_errors(grads["pallas"][1], grads["blocked"][1])
    worst = max(errors, key=errors.get)
    log(f"train: MoE-block gradients pallas vs blocked, {len(errors)} "
        f"leaves, largest relative L2 error {errors[worst]:.2e} at {worst} "
        f"(limit {TRAIN_GRAD_REL_L2})")
    if not errors[worst] <= TRAIN_GRAD_REL_L2:
        raise AssertionError("train: pallas and blocked gradients disagree")
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, images, labels)
        losses.append(float(loss))  # float() waits for the step
        times.append(time.perf_counter() - t0)
    log(f"train: losses {losses}")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"train: non-finite loss in {losses}")
    err = abs(losses[0] - ref_loss) / abs(ref_loss)
    log(f"train: step-1 loss pallas {losses[0]:.6f} vs blocked "
        f"{ref_loss:.6f}, relative error {err:.2e} (limit {TRAIN_RTOL})")
    if not err <= TRAIN_RTOL:
        raise AssertionError("train: pallas and blocked losses disagree")
    warm = times[1:] or times
    log(f"train (smoke, not a benchmark): step times "
        f"{[round(t, 4) for t in times]} s; median after the first "
        f"{np.median(warm):.4f} s = {batch / np.median(warm):.1f} images/s")


def check_logits(name: str, got, want) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    rel = rel_l2(got, want)
    same = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"serve: {name} logits pallas vs blocked, relative L2 error "
        f"{rel:.2e} (limit {SERVE_REL_L2}); argmax agrees on "
        f"{same:.0%} of rows")
    if not (np.all(np.isfinite(got)) and rel <= SERVE_REL_L2):
        raise AssertionError(f"serve: pallas and blocked {name} disagree")


def serve_phase(cfg, *, requests: int, prompt_len: int, max_new: int,
                page_size: int, seed: int) -> None:
    from repro.launch import serve, steps as steps_lib
    from repro.models import lm
    from repro.parallel.sharding import ParallelConfig, split_tree

    # As launch/serve.py builds it for a paged run, with the kernel path
    # named explicitly.
    pcfg = ParallelConfig(mode="auto", blk=16, impl="pallas")
    ref_pcfg = dataclasses.replace(pcfg, impl="blocked")
    params = jax.jit(lambda k: split_tree(lm.init_params(k, cfg))[0])(
        jax.random.PRNGKey(seed))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"serve: {cfg.name} x{cfg.num_layers} layers, "
        f"{n_bytes / 1e9:.2f} GB of weights")
    max_seq = prompt_len + max_new
    maxp = cdiv(max_seq, page_size)
    server = serve.PagedServer(
        cfg, pcfg, None, num_slots=requests, page_size=page_size,
        num_pages=requests * maxp + 1, max_pages_per_slot=maxp,
        params=params, prefill_chunk=prompt_len,
    )
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(requests, prompt_len))
    prompts = prompts.astype(np.int32)

    # The engine's own jitted steps against the blocked ones, on caches the
    # engine's prefill fills: bfloat16 and int8 KV pages.
    table = 1 + np.arange(requests * maxp, dtype=np.int32).reshape(
        requests, maxp)
    ref_prefill = steps_lib.make_paged_prefill_step(
        cfg, ref_pcfg, None, page_size)
    ref_decode = steps_lib.make_paged_serve_step(
        cfg, ref_pcfg, None, (requests, 1, cfg.d_model), page_size)
    for kv_quant in (None, "int8"):
        kv = kv_quant or cfg.dtype
        cache = lm.init_paged_cache(cfg, requests, requests * maxp + 1,
                                    page_size, kv_quant=kv_quant)
        args = (params, jnp.asarray(prompts[0]), jnp.int32(prompt_len),
                jnp.int32(0), jnp.asarray(table[0]), cache)
        prefill = compile_checked(f"prefill step ({kv} KV, pallas)",
                                  server.prefill_step, *args)
        want = compile_checked(f"prefill step ({kv} KV, blocked)",
                               ref_prefill, *args, kernels=False)(*args)[0]
        firsts = []
        for i in range(requests):
            logits, cache = prefill(params, jnp.asarray(prompts[i]),
                                    jnp.int32(prompt_len), jnp.int32(i),
                                    jnp.asarray(table[i]), cache)
            firsts.append(int(jnp.argmax(logits)))
            if i == 0:
                got = logits
        check_logits(f"{kv} KV prefill", got, want)
        args = (params, {"tokens": jnp.asarray(firsts, jnp.int32)[:, None],
                         "page_table": jnp.asarray(table),
                         "active": jnp.ones((requests,), bool)}, cache)
        got = compile_checked(f"decode step ({kv} KV, pallas)",
                              server.serve_step, *args)(*args)[0]
        want = compile_checked(f"decode step ({kv} KV, blocked)",
                               ref_decode, *args, kernels=False)(*args)[0]
        check_logits(f"{kv} KV decode, {requests} slots", got, want)

    for i in range(requests):
        server.submit(serve.Request(rid=i, prompt=prompts[i],
                                    max_new=max_new))
    t0 = time.perf_counter()
    done = server.run()
    wall = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in done)
    if len(done) != requests or tokens != requests * max_new:
        raise AssertionError(
            f"serve: {len(done)} of {requests} requests done, {tokens} "
            f"tokens of {requests * max_new}")
    ts = np.asarray(server.decode_times_s[1:] or server.decode_times_s)
    ttft = sorted(server.ttft_s.values())
    log(f"serve (smoke, not a benchmark): {requests} requests, {tokens} "
        f"tokens in {wall:.2f} s ({tokens / wall:.1f} tokens/s, first "
        f"jit calls included); decode step median {np.median(ts) * 1e3:.2f}"
        f" ms = {requests / np.median(ts):.1f} tokens/s over {len(ts)} "
        f"steps; TTFT median {ttft[len(ttft) // 2] * 1e3:.1f} ms")
    log(f"serve: first request's tokens {done[0].out}")


def four_chip_phase(cfg, *, batch: int, seq: int, steps: int,
                    seed: int) -> None:
    from repro.launch import inputs as inputs_lib, steps as steps_lib
    from repro.launch.mesh import make_mesh
    from repro.models import lm
    from repro.optim import adamw
    from repro.parallel.sharding import ParallelConfig, split_tree
    from repro.configs.base import ShapeConfig
    from repro.quant.core import EXPERT_WEIGHT_KEYS

    mesh = make_mesh((2, 2), ("data", "model"))
    opt_cfg = adamw.OptimizerConfig(master_fp32=False)
    shape = ShapeConfig("smoke", "train", seq, batch)
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    host_batch = {"tokens": toks, "labels": np.roll(toks, -1, 1),
                  "loss_mask": np.ones((batch, seq), np.float32)}
    init = None
    results = {}
    for mode in ("data_centric", "model_centric"):
        pcfg = ParallelConfig(mode=mode, impl="pallas")
        _, shardings, _ = steps_lib.sharded_params(cfg, pcfg, mesh)
        if init is None:  # one draw of the weights, resharded per mode
            init = jax.jit(
                lambda k: split_tree(lm.init_params(k, cfg))[0],
                out_shardings=shardings)(jax.random.PRNGKey(seed))
        # A copy: the step donates its params, and init serves both modes.
        params = jax.device_put(init, shardings, may_alias=False)
        specs = inputs_lib.input_specs(cfg, shape, pcfg, mesh)
        data = {k: jax.device_put(v, specs[k].sharding)
                for k, v in host_batch.items()}
        opt = adamw.init_opt_state(params, opt_cfg)
        with mesh:
            step = compile_checked(
                f"train step ({mode}, 2x2 mesh)",
                steps_lib.make_train_step(cfg, pcfg, mesh, opt_cfg,
                                          (batch, seq, cfg.d_model)),
                params, opt, data, donate=(0, 1))
            losses, times = [], []
            for _ in range(steps):
                t0 = time.perf_counter()
                params, opt, metrics = step(params, opt, data)
                losses.append(float(metrics["loss"]))
                times.append(time.perf_counter() - t0)
        expert = [0] * len(mesh.devices.flat)
        index = {d: i for i, d in enumerate(mesh.devices.flat)}
        for path, leaf in jax.tree_util.tree_leaves_with_path(params):
            if getattr(path[-1], "key", None) in EXPERT_WEIGHT_KEYS:
                for shard in leaf.addressable_shards:
                    expert[index[shard.device]] += shard.data.nbytes
        in_use = [bytes_in_use(d) for d in mesh.devices.flat]
        log(f"{mode}: losses {losses}; step times "
            f"{[round(t, 4) for t in times]} s (smoke)")
        log(f"{mode}: expert bytes per device {expert}; memory_stats "
            f"bytes_in_use {in_use}")
        if not all(np.isfinite(losses)):
            raise AssertionError(f"{mode}: non-finite loss in {losses}")
        if min(expert) == 0 or any(u < e for u, e in zip(in_use, expert)):
            raise AssertionError(f"{mode}: expert weights not on all chips")
        results[mode] = losses
        del params, opt, step
    dc, mc = results["data_centric"], results["model_centric"]
    err = max(abs(a - b) / abs(b) for a, b in zip(dc, mc))
    log(f"data- vs model-centric: max relative loss difference {err:.2e} "
        f"(limit {CENTRIC_RTOL})")
    if not err <= CENTRIC_RTOL:
        raise AssertionError("data- and model-centric losses disagree")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded MoE training path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        sys.exit(f"chip_smoke: needs {want} TPU chip(s); JAX found "
                 f"{len(devices)} {devices[0].platform} device(s)")
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}")

    from repro import configs as cfglib
    from repro.configs import swin_moe_small

    qwen = cfglib.get_config("qwen3-moe-30b-a3b")
    if args.four_chips:
        four_chip_phase(dataclasses.replace(qwen, num_layers=1),
                        batch=8, seq=512, steps=3, seed=args.seed)
    else:
        train_phase(swin_moe_small.CONFIG, batch=32, steps=5, seed=args.seed)
        serve_phase(dataclasses.replace(qwen, num_layers=4), requests=8,
                    prompt_len=32, max_new=16, page_size=16, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
