"""Training driver: data pipeline -> jitted train step -> checkpoints,
with fault tolerance (restore-on-failure, preemption save), straggler
monitoring feeding the heterogeneous planner, and deterministic resume.

CPU-scale example (examples/ use this):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-30b-a3b \
      --smoke --steps 50 --global-batch 8 --seq-len 256

On a real slice the same driver runs the full config across the production
mesh (--mesh data,model) — everything else is identical.

Heterogeneous execution (paper §4.4, DESIGN.md §6): ``--hetero-latencies``
builds an Eq. 1/2 ``HeteroPlan`` that the MoE islands execute (uneven
per-device token shares, padded + masked; uneven TP hidden tiles via
``--hetero-tp-latencies``). ``--hetero-replan`` closes the straggler loop:
observed step times re-plan the token shares online, each new plan being a
bounded re-trace through ``parallel.cache.PlanCache``. ``--simulate-skew``
synthesises the per-device telemetry on a single host so the loop can be
demonstrated (and tested) off-cluster.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro import obs
from repro.checkpoint import manager as ckpt
from repro.common import enable_compile_cache
from repro.configs.base import ShapeConfig
from repro.core import hetero as hetero_lib
from repro.data.pipeline import DataConfig, Prefetcher, TokenSource
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_mesh, split_model_axis
from repro.models import lm
from repro.parallel.cache import PlanCache
from repro.optim import adamw
from repro.parallel.sharding import ParallelConfig, split_tree, tree_shardings
from repro.runtime import elastic as elastic_lib
from repro.runtime import faults as faults_lib
from repro.runtime import ft as ft_lib
from repro.runtime.straggler import StragglerConfig, StragglerMonitor


def build_state(cfg, pcfg, mesh, opt_cfg, seed):
    """Init sharded params (hetero-plan-padded when attached) + AdamW
    optimizer state."""
    params_p = lm.init_params(
        jax.random.PRNGKey(seed), cfg, plan=pcfg.hetero_plan
    )
    params, specs = split_tree(params_p)
    if mesh is not None:
        sh = tree_shardings(params, specs, pcfg, mesh)
        params = jax.tree.map(jax.device_put, params, sh)
    opt_state = adamw.init_opt_state(params, opt_cfg)
    return params, opt_state


def main(argv=None):
    """CLI training driver: synthetic-data train loop with optional mesh,
    hetero plan, straggler monitor, and QAT fake-quant."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--mesh", default=None, help="e.g. '2,4' => data,model")
    ap.add_argument("--mode", default="hybrid",
                    choices=["hybrid", "model_centric", "data_centric",
                             "auto", "ep"],
                    help="'auto' picks data/model-centric per MoE layer "
                         "from the roofline (parallel.autotune)")
    ap.add_argument("--schedule", default="ag_rs")
    ap.add_argument("--cache-policy", default="shared_cache")
    ap.add_argument("--cache-layers", type=int, default=0,
                    help="pipeline-shared prefetch cache residency bound "
                         "(gathered MoE periods); >0 implies --no-scan. "
                         "Inference-side mechanism: the remat'd train step "
                         "itself keeps using the remat-policy cache "
                         "(gathered params re-gathered in backward), so "
                         "this mainly affects eval/serve-style forwards")
    ap.add_argument("--no-scan", action="store_true",
                    help="unroll the period loop instead of lax.scan")
    ap.add_argument("--proxy-latencies", default=None,
                    help="comma-separated per-device proxy latencies t_i "
                         "(core.hetero); makes the auto chooser "
                         "heterogeneity-aware")
    ap.add_argument("--hetero-latencies", default=None,
                    help="comma-separated t_i per BATCH-group member: build "
                         "and EXECUTE an Eq. 1 uneven token split "
                         "(core.hetero.HeteroPlan; DESIGN.md §6). Requires "
                         "--mesh")
    ap.add_argument("--hetero-tp-latencies", default=None,
                    help="comma-separated t_i per TP-group member: adds the "
                         "Eq. 2 uneven hidden split (padded MXU tiles) to "
                         "the plan")
    ap.add_argument("--hetero-replan", action="store_true",
                    help="close the straggler loop: observed step times "
                         "re-plan the Eq. 1 shares online; each distinct "
                         "plan is one bounded re-trace (PlanCache)")
    ap.add_argument("--simulate-skew", default=None,
                    help="comma-separated per-worker slowdown factors used "
                         "to synthesise per-device telemetry on a single "
                         "host (demo/test of the replan loop)")
    ap.add_argument("--quant", default="none",
                    choices=["none", "int8", "fp8"],
                    help="QAT: expert weights pass through block-wise "
                         "int8/fp8 fake-quant inside the MoE islands "
                         "(straight-through grads; routers/dense layers "
                         "stay full precision — DESIGN.md §8)")
    ap.add_argument("--topology", default=None,
                    help="intra_bw:inter_bw:node_size (e.g. 50e9:12.5e9:4) "
                         "— two-level interconnect (DESIGN.md §10). Prices "
                         "the auto chooser's collectives per level, and "
                         "when the mesh's model extent spans multiple "
                         "nodes, splits it into ('node','model') and runs "
                         "the MoE islands' hierarchical dispatch "
                         "(node-local combine before the cross-node "
                         "exchange)")
    ap.add_argument("--overlap-dispatch", action="store_true",
                    help="overlap the NEXT MoE layer's expert collectives "
                         "with the current layer's compute: the "
                         "pipeline-shared prefetcher gathers data-centric "
                         "layers' full expert weights (fsdp AND tp factor) "
                         "a period ahead (DESIGN.md §10). Requires "
                         "--cache-layers > 0 and --mode auto")
    ap.add_argument("--impl", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable the metrics registry and dump a "
                         "Prometheus text snapshot to PATH at exit "
                         "(DESIGN.md §12); also turns on per-expert "
                         "router telemetry as extra train-step outputs")
    ap.add_argument("--metrics-interval", type=int, default=0, metavar="N",
                    help="also dump the Prometheus snapshot every N steps "
                         "(0 = exit-only)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record train-loop spans and write a Chrome "
                         "trace-event JSON (Perfetto-loadable) to PATH")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="write the structured event log (replans, "
                         "recoveries; JSONL) to PATH")
    ap.add_argument("--fault-spec", default=None,
                    help="chaos fault plan: inline JSON or a JSON file "
                         "(runtime.faults; sites train.step / train.loss / "
                         "train.preempt / ckpt.write, DESIGN.md §9)")
    ap.add_argument("--elastic", action="store_true",
                    help="on device dropout, re-mesh over the survivors "
                         "(runtime.elastic.choose_mesh_shape), re-derive "
                         "the hetero plan's token shares, and resume from "
                         "the newest valid checkpoint (requires --mesh)")
    args = ap.parse_args(argv)
    if args.elastic and not args.mesh:
        ap.error("--elastic requires --mesh (nothing to re-mesh)")
    if args.fault_spec:
        faults_lib.install(faults_lib.load_plan(args.fault_spec))

    obs_on = bool(args.metrics or args.trace_out or args.events_out)
    if obs_on:
        obs.configure(metrics=bool(args.metrics),
                      tracing=bool(args.trace_out),
                      event_log=bool(args.events_out), reset=True)

    cfg = (cfglib.get_smoke_config(args.arch) if args.smoke
           else cfglib.get_config(args.arch))
    if args.smoke:
        # widen the smoke vocab if the tokenizer stream needs it
        pass

    topo = None
    if args.topology:
        from repro.parallel.autotune import Topology
        try:
            topo = Topology.parse(args.topology)
        except (ValueError, TypeError) as e:
            ap.error(f"--topology: {e}")
    if args.overlap_dispatch and args.cache_layers <= 0:
        ap.error("--overlap-dispatch requires --cache-layers > 0 (the "
                 "prefetcher lives in the pipeline-shared cache)")

    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        axes = ("pod", "data", "model")[-len(dims):]
        if topo is not None:
            dims, axes = split_model_axis(dims, axes, topo.node_size)
        mesh = make_mesh(dims, axes)

    latencies = None
    if args.proxy_latencies:
        try:
            latencies = tuple(
                float(t) for t in args.proxy_latencies.split(",")
            )
        except ValueError:
            ap.error("--proxy-latencies must be comma-separated numbers")
        if any(t <= 0 for t in latencies):
            ap.error("--proxy-latencies must all be positive (seconds)")
    pcfg = ParallelConfig(
        mode=args.mode,
        collective_schedule=args.schedule,
        cache_policy=args.cache_policy,
        cache_layers=args.cache_layers,
        scan_layers=not (args.no_scan or args.cache_layers > 0),
        device_latencies=latencies,
        impl=args.impl,
        blk=min(128, max(16, args.seq_len // 4)),
        quant=args.quant,
        topology=topo,
        overlap_dispatch=args.overlap_dispatch,
        # --metrics adds per-expert router telemetry to the step outputs
        collect_router_stats=bool(args.metrics) and cfg.moe is not None,
    )

    def parse_lat(s, flag):
        try:
            vals = tuple(float(t) for t in s.split(","))
        except ValueError:
            ap.error(f"{flag} must be comma-separated numbers")
        if any(t <= 0 for t in vals):
            ap.error(f"{flag} must all be positive (seconds)")
        return vals

    hetero_plan = None
    if args.hetero_latencies:
        if mesh is None:
            ap.error("--hetero-latencies requires --mesh")
        tok_lat = parse_lat(args.hetero_latencies, "--hetero-latencies")
        tp_lat = (parse_lat(args.hetero_tp_latencies, "--hetero-tp-latencies")
                  if args.hetero_tp_latencies else None)
        hetero_plan = hetero_lib.make_hetero_plan(
            tok_lat,
            global_batch=args.global_batch,
            hidden_size=(cfg.moe.d_ff
                         if tp_lat is not None and cfg.moe is not None
                         else None),
            tp_latencies=tp_lat,
            capacity_headroom=1.5 if args.hetero_replan else 1.0,
        )
        pcfg = dataclasses.replace(pcfg, hetero_plan=hetero_plan)
        print(f"[hetero] plan: token_counts={hetero_plan.token_counts} "
              f"(capacity {hetero_plan.batch_capacity}/device) "
              f"hidden_splits={hetero_plan.hidden_splits}")
    opt_cfg = adamw.OptimizerConfig(
        peak_lr=args.lr, warmup_steps=args.warmup,
        decay_steps=max(args.steps, 2 * args.warmup),
        master_fp32=True,
    )
    data_cfg = DataConfig(
        seq_len=args.seq_len, global_batch=args.global_batch,
        vocab_size=cfg.vocab_size, seed=args.seed,
    )
    source = TokenSource(data_cfg)

    params, opt_state = build_state(cfg, pcfg, mesh, opt_cfg, args.seed)
    # Uneven plans pad the SPMD batch: n_devices * capacity rows, of which
    # each device's Eq. 1 share is real (DESIGN.md §6). Shapes are FIXED
    # across replans — only the (plan-keyed) trace changes.
    eff_batch = args.global_batch
    if hetero_plan is not None and hetero_plan.token_counts is not None:
        eff_batch = (len(hetero_plan.token_counts)
                     * hetero_plan.batch_capacity)
    shape3 = (eff_batch, args.seq_len, cfg.d_model)
    plan_cache = PlanCache(4)
    # Single-element boxes: the replan loop and the elastic device-loss
    # handler both swap the live mesh/shape/step without re-entering main.
    mesh_box = [mesh]
    shape_box = [shape3]
    mesh_gen = [0]   # bumped per re-mesh so PlanCache keys can't collide

    def jit_step_for(plan):
        def build():
            pc = dataclasses.replace(pcfg, hetero_plan=plan)
            return jax.jit(
                steps_lib.make_train_step(
                    cfg, pc, mesh_box[0], opt_cfg, shape_box[0]),
                donate_argnums=(0, 1),
            )
        key = (mesh_gen[0], None if plan is None else plan.key())
        # The compiled step gets the chaos wrapper OUTSIDE the cache:
        # injection is host-level (inside jit it would fire at trace time
        # only) and must not be memoized away with the trace.
        return steps_lib.wrap_step_with_faults(
            plan_cache.fetch(key, build), "train.step")

    cur_plan = [hetero_plan]
    jit_step_box = [jit_step_for(hetero_plan)]

    start_step = 0
    state = {"params": params, "opt": opt_state}
    if args.resume:
        last = ckpt.latest_valid_step(args.ckpt_dir)
        if last is not None:
            state, meta = ckpt.restore(args.ckpt_dir, last, state)
            start_step = int(meta["step"])
            print(f"[train] resumed from step {start_step}")

    n_workers = 1
    if hetero_plan is not None and hetero_plan.token_counts is not None:
        n_workers = len(hetero_plan.token_counts)
    monitor = StragglerMonitor(
        num_workers=n_workers, global_batch=args.global_batch,
        cfg=StragglerConfig(window=8, min_steps_between_replans=8),
        plan=hetero_plan,
    )
    sim_skew = None
    if args.simulate_skew:
        sim_skew = np.asarray(
            parse_lat(args.simulate_skew, "--simulate-skew"))
        if len(sim_skew) != n_workers:
            ap.error(f"--simulate-skew needs {n_workers} factors")
    metrics_log = []
    t_last = [time.time()]
    router_drain = None
    if pcfg.collect_router_stats:
        router_drain = obs.RouterStatsDrain(
            obs.registry, cfg.moe.num_experts, phase="train")

    def dump_obs_metrics():
        if not args.metrics:
            return
        if router_drain is not None:
            router_drain.flush()
        obs.registry.collect()
        obs.dump_prometheus(obs.registry, args.metrics)

    def step_fn(state, step):
        t_data0 = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in source.batch(step).items()}
        if cfg.frontend == "encodec":
            rngb = np.random.default_rng(step)
            b, s = batch["tokens"].shape
            batch = {
                "embeds": jnp.asarray(
                    rngb.normal(size=(b, s, cfg.frontend_dim)), jnp.float32),
                "cond": jnp.asarray(
                    rngb.normal(size=(b, 64, cfg.cross_d)), jnp.float32),
                "labels": jnp.repeat(
                    batch["labels"][..., None], cfg.num_codebooks, -1),
                "loss_mask": batch["loss_mask"],
            }
        elif cfg.frontend == "siglip":
            rngb = np.random.default_rng(step)
            b, s = batch["tokens"].shape
            npatch = cfg.prefix_len
            batch = {
                "patches": jnp.asarray(
                    rngb.normal(size=(b, npatch, cfg.frontend_dim)), jnp.float32),
                "tokens": batch["tokens"][:, : s - npatch],
                "labels": batch["labels"],
                "loss_mask": batch["loss_mask"],
            }
        plan = cur_plan[0]
        if plan is not None and plan.token_counts is not None:
            # Re-pack the host batch into the plan's padded layout (each
            # device's Eq. 1 share followed by masked tail rows).
            batch = {
                k: jnp.asarray(v) for k, v in hetero_lib.pack_batch(
                    {k: np.asarray(v) for k, v in batch.items()}, plan
                ).items()
            }
        obs.tracer.complete("train.data", t_data0, time.perf_counter(),
                            step=step)
        with obs.tracer.span("train.step", step=step):
            params, opt, m = jit_step_box[0](
                state["params"], state["opt"], batch)
            # The device-side router accumulators ride the metrics pytree as
            # a non-scalar entry; hand them to the async drain before the
            # scalar float() conversion below.
            rstats = m.pop("router_stats", None)
            if rstats is not None and router_drain is not None:
                router_drain.push(rstats)
            m = {k: float(v) for k, v in m.items()}
        now = time.time()
        m["step_time_s"] = now - t_last[0]
        t_last[0] = now
        obs.registry.histogram(
            "repro_train_step_seconds",
            "Wall time per optimiser step").observe(m["step_time_s"])
        # Per-worker telemetry: real deployments feed host timings here; a
        # single-host demo synthesises them from the wall time, the plan
        # shares, and the simulated skew (time_i ∝ share_i * skew_i).
        times = [m["step_time_s"]] * n_workers
        if sim_skew is not None:
            shares = np.asarray(
                plan.token_counts if plan is not None
                and plan.token_counts is not None else [1] * n_workers,
                np.float64,
            )
            w = np.maximum(shares, 1e-9) * sim_skew
            times = list(m["step_time_s"] * w / w.mean())
        new_shares = monitor.report(times)
        if new_shares is not None and args.hetero_replan and plan is not None:
            cur_plan[0] = monitor.current_plan()
            jit_step_box[0] = jit_step_for(cur_plan[0])
            st = plan_cache.stats()
            obs.events.emit("train.replan", reason="straggler",
                            step=step, shares=list(new_shares))
            print(f"[hetero] replan -> shares {new_shares} "
                  f"(traces: {st['misses']}, reused: {st['hits']})")
        return {"params": params, "opt": opt}, m

    def on_metrics(step, m):
        metrics_log.append({"step": step, **m})
        if args.metrics_interval and step % args.metrics_interval == 0:
            dump_obs_metrics()
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {m['loss']:.4f} "
                  f"aux {m.get('aux_loss', 0):.4f} lr {m['lr']:.2e} "
                  f"({m['step_time_s']:.2f}s)")

    on_device_loss = None
    if args.elastic:
        def on_device_loss(err):
            """Elastic shrink (DESIGN.md §9): re-mesh over the survivors,
            re-derive the plan's token shares (hidden_splits stay fixed —
            they pad param shapes, and the checkpoint must still load),
            swap in a freshly-jitted step, and hand ``run_with_recovery``
            the template to restore the newest valid checkpoint into."""
            nonlocal n_workers, monitor, sim_skew
            survivors = err.survivors
            devs = (list(jax.devices())[:int(survivors)]
                    if isinstance(survivors, int)
                    else [jax.devices()[int(i)] for i in survivors])
            if not devs:
                raise RuntimeError("device dropout left no devices") \
                    from err
            new_shape = elastic_lib.choose_mesh_shape(len(devs))
            mesh_box[0] = elastic_lib.make_mesh(
                new_shape, ("data", "model"), devices=devs)
            mesh_gen[0] += 1
            new_plan = cur_plan[0]
            if (new_plan is not None and new_plan.token_counts is not None
                    and not isinstance(survivors, int)):
                # Re-derive ONLY the Eq. 1 token shares over the surviving
                # classes; hidden_splits/expert_bits pad the param shapes
                # and must stay fixed or the checkpoint could not load.
                surv_lat = tuple(new_plan.proxy_latencies[int(i)]
                                 for i in survivors)
                tmp = hetero_lib.make_hetero_plan(
                    surv_lat, global_batch=args.global_batch)
                new_plan = dataclasses.replace(
                    new_plan, proxy_latencies=tmp.proxy_latencies,
                    token_counts=tmp.token_counts,
                    token_capacity=tmp.token_capacity)
                shape_box[0] = (
                    len(new_plan.token_counts) * new_plan.batch_capacity,
                    args.seq_len, cfg.d_model)
            cur_plan[0] = new_plan
            if new_plan is not None and new_plan.token_counts is not None:
                n_workers = len(new_plan.token_counts)
                monitor = StragglerMonitor(
                    num_workers=n_workers, global_batch=args.global_batch,
                    cfg=StragglerConfig(window=8,
                                        min_steps_between_replans=8),
                    plan=new_plan)
                if sim_skew is not None and not isinstance(survivors, int):
                    sim_skew = sim_skew[[int(i) for i in survivors]]
            jit_step_box[0] = jit_step_for(new_plan)
            obs.events.emit("train.shrink", reason="device dropout",
                            mesh_shape=list(new_shape),
                            survivors=len(devs))
            print(f"[elastic] device loss -> re-mesh {new_shape} over "
                  f"{len(devs)} survivors")
            return state, None

    ft_cfg = ft_lib.FTConfig(
        ckpt_dir=args.ckpt_dir, save_every=args.save_every
    )
    with obs.tracer.span("train.run", steps=args.steps):
        state, last = ft_lib.run_with_recovery(
            state=state, step_fn=step_fn, start_step=start_step,
            num_steps=args.steps, ft=ft_cfg, on_metrics=on_metrics,
            on_device_loss=on_device_loss,
        )
    faults_lib.install(None)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(metrics_log, f, indent=1)
    if args.metrics:
        dump_obs_metrics()
        print(f"[obs] prometheus metrics -> {args.metrics}")
    if args.trace_out:
        obs.tracer.write(args.trace_out)
        cov = obs.span_coverage(obs.tracer.events)
        print(f"[obs] chrome trace -> {args.trace_out} "
              f"({len(obs.tracer.events)} events, "
              f"span coverage {cov:.1%})")
    if args.events_out:
        obs.events.write_jsonl(args.events_out)
        print(f"[obs] event log -> {args.events_out} "
              f"({len(obs.events.records)} records)")
    print(f"[train] finished at step {last}; "
          f"final loss {metrics_log[-1]['loss']:.4f}"
          if metrics_log else "[train] no steps run")
    return metrics_log


if __name__ == "__main__":
    enable_compile_cache()
    main()
