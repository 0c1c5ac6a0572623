"""Multi-pod dry-run driver: compile (not execute) the paper-scale
training/decode cells on a host-platform device farm, reporting per-cell
parallel-config choices, HLO collective counts, and memory estimates."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count on first init,
# and the host-device farm is the CPU backend's (never a chip's).

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402

if __name__ == "__main__" and "--devices" in sys.argv:
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_n}"

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402

from repro import configs as cfglib                         # noqa: E402
from repro.configs.base import LONG_CONTEXT_ARCHS, SHAPES   # noqa: E402
from repro.launch import inputs as inputs_lib               # noqa: E402
from repro.launch import steps as steps_lib                 # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402
from repro.optim import adamw                               # noqa: E402
from repro.parallel.autotune import V5E                     # noqa: E402
from repro.parallel.sharding import ParallelConfig          # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline terms from the compiled artifact.

No arrays are ever allocated: parameters, optimizer state, batches and
caches are ShapeDtypeStructs with NamedShardings. ``compile()`` proving the
sharding is coherent (no mismatch, no unsupported collective) and
``memory_analysis()`` proving it fits are the deliverable; cost/collective
numbers feed EXPERIMENTS.md §Roofline.
"""

# v5e hardware model (per chip), from the one table in parallel.autotune
PEAK_FLOPS = V5E.peak_flops
HBM_BW = V5E.hbm_bw
LINK_BW = V5E.link_bw

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*\("
)
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2, "u16": 2, "f8e4m3": 1,
    "f8e5m2": 1,
}


def parse_collectives(hlo_text: str, loop_multipliers: dict) -> dict:
    """Sum shard-local collective bytes from the partitioned HLO.

    Collectives inside while-loop bodies are multiplied by the loop's trip
    count (the layer scan); outside they count once. Wire model: all-reduce
    2x (reduce + broadcast phases), others 1x the result bytes.
    """
    per_op: dict = {}
    current_comp = "<main>"
    mult = 1
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ("%" in stripped or stripped.startswith("ENTRY")):
            header = stripped.split("(")[0]
            current_comp = header.replace("%", "").strip()
            mult = 1
            for key, m in loop_multipliers.items():
                if key in current_comp:
                    mult = m
                    break
        m = COLLECTIVE_RE.search(stripped)
        if not m or "=" not in stripped:
            continue
        kind = m.group(1)
        lhs = stripped.split("=", 1)[1]
        sm = SHAPE_RE.search(lhs)
        if sm is None:
            continue
        dtype, dims = sm.group(1), sm.group(2)
        if dtype == "tuple" or dtype not in DTYPE_BYTES:
            # tuple results: sum every shape in the tuple
            total = 0
            for dt, ds in SHAPE_RE.findall(lhs.split(kind)[0]):
                if dt in DTYPE_BYTES:
                    n = int(np.prod([int(x) for x in ds.split(",") if x])) if ds else 1
                    total += n * DTYPE_BYTES[dt]
            size = total
        else:
            n = int(np.prod([int(x) for x in dims.split(",") if x])) if dims else 1
            size = n * DTYPE_BYTES[dtype]
        factor = 2 if kind == "all-reduce" else 1
        rec = per_op.setdefault(kind, {"bytes": 0, "count": 0})
        rec["bytes"] += size * factor * mult
        rec["count"] += mult
    return per_op


def find_loop_multipliers(hlo_text: str, n_periods: int) -> dict:
    """Map while-body computation names -> trip count. The layer scan (and
    its transpose in backward) dominates; inner scans carry no collectives,
    so attributing every while body the scan trip count is exact for our
    programs (verified against unrolled small configs in tests)."""
    mults = {}
    for m in re.finditer(r"%?(body[\w.\-]*|while_body[\w.\-]*)\s*\(", hlo_text):
        mults[m.group(1)] = n_periods
    return mults


def default_pcfg(cfg, shape, args) -> ParallelConfig:
    """Pick the per-cell parallel mode/layout the way the paper's runtime
    would: decode prefers model-centric when one TP shard fits HBM."""
    blk = 128  # MXU-aligned; padding <= E*(blk-1) stays <5% for all cells
    mode = args.mode
    if mode == "auto":
        if shape.kind == "decode":
            # replicate over data only if one TP shard of params fits HBM
            pbytes = cfg.param_count() * 2
            mode = "model_centric" if pbytes / 16 <= 8e9 else "hybrid"
        else:
            mode = "hybrid"
    # Layers are UNROLLED by default for the roofline cells: XLA's
    # cost_analysis does not multiply while-body FLOPs by trip count, so a
    # scanned program under-reports compute ~n_periods-fold. Unrolling makes
    # flops/bytes/collectives exact; --scan keeps the HLO small (multi-pod
    # pass/fail cells, big compile jobs).
    unroll = not args.scan
    return ParallelConfig(
        mode=mode,
        collective_schedule=args.schedule,
        cache_policy=args.cache_policy,
        remat="block",
        blk=blk if shape.kind != "decode" else 8,
        impl="blocked",
        scan_layers=not unroll,
    )


def default_opt_cfg(cfg, n_chips) -> adamw.OptimizerConfig:
    """Optimizer precision by memory pressure: bf16 state once fp32
    master + moments would exceed the per-chip HBM budget."""
    pbytes14 = cfg.param_count() * 14
    if pbytes14 / n_chips > 12e9:
        return adamw.OptimizerConfig(state_dtype="bfloat16", master_fp32=False)
    return adamw.OptimizerConfig(state_dtype="float32", master_fp32=True)


def _lower_one(cfg, shape, pcfg, opt_cfg, mesh):
    """Lower + compile one step program; returns (compiled, t_lower, t_comp)."""
    t0 = time.time()
    abstract_params, _, _ = steps_lib.sharded_params(cfg, pcfg, mesh)
    batch = inputs_lib.input_specs(cfg, shape, pcfg, mesh)
    if shape.kind == "train":
        shape3 = (shape.global_batch, shape.seq_len, cfg.d_model)
        opt_state = steps_lib.sharded_opt_state(abstract_params, opt_cfg, mesh)
        step_fn = steps_lib.make_train_step(cfg, pcfg, mesh, opt_cfg, shape3)
        with mesh:
            lowered = jax.jit(step_fn).lower(abstract_params, opt_state, batch)
    elif shape.kind == "prefill":
        shape3 = (shape.global_batch, shape.seq_len, cfg.d_model)
        cache = inputs_lib.cache_specs(cfg, shape, pcfg, mesh)
        step_fn = steps_lib.make_prefill_step(cfg, pcfg, mesh, shape3)
        with mesh:
            lowered = jax.jit(step_fn).lower(abstract_params, batch, cache)
    else:
        shape3 = (shape.global_batch, 1, cfg.d_model)
        cache = inputs_lib.cache_specs(cfg, shape, pcfg, mesh)
        step_fn = steps_lib.make_serve_step(cfg, pcfg, mesh, shape3)
        with mesh:
            lowered = jax.jit(step_fn).lower(abstract_params, batch, cache)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    return compiled, t_lower, time.time() - t0


def _extract(compiled):
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = parse_collectives(hlo, {})
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "colls": colls,
        "hlo": hlo,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, args) -> dict:
    """Compile one (arch, shape) cell on the virtual mesh and return its
    report row (mode, collectives, padding, memory estimates)."""
    import dataclasses

    cfg = cfglib.get_config(arch)
    shape = SHAPES[shape_name]
    canon = cfglib.canonical(arch)
    if args.layers_override:
        n = args.layers_override
        n = max(cfg.period, n - n % cfg.period)
        cfg = dataclasses.replace(cfg, num_layers=n)

    if shape_name == "long_500k" and canon not in LONG_CONTEXT_ARCHS:
        return {"status": "skipped",
                "reason": "pure full-attention arch; see DESIGN.md §4"}

    n_dev = len(jax.devices())
    if args.mesh_shape:
        dims = tuple(int(x) for x in args.mesh_shape.split(","))
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = make_mesh(dims, axes)
    elif n_dev == 512:
        mesh = make_production_mesh(multi_pod=multi_pod)
        if not multi_pod:
            mesh = make_mesh((16, 16), ("data", "model"))
    else:  # debug pools
        if multi_pod:
            mesh = make_mesh((2, n_dev // 4, 2), ("pod", "data", "model"))
        else:
            mesh = make_mesh((n_dev // 2, 2), ("data", "model"))
    n_chips = int(np.prod(list(mesh.shape.values())))

    pcfg = default_pcfg(cfg, shape, args)
    opt_cfg = default_opt_cfg(cfg, n_chips)

    # Exact parameter counts from the abstract tree (not the config
    # heuristic): N and N_active for the §Roofline MODEL_FLOPS convention.
    from repro.common import tree_params
    abstract_params, _, _ = steps_lib.sharded_params(cfg, pcfg, mesh)
    n_total = tree_params(abstract_params)
    if cfg.moe is not None:
        n_moe_layers = sum(
            cfg.is_moe_layer(i) for i in range(cfg.num_layers)
        )
        n_mats = 3 if cfg.glu else 2
        inactive = (
            n_moe_layers
            * (cfg.moe.num_experts - cfg.moe.top_k)
            * n_mats * cfg.d_model * cfg.moe.d_ff
        )
        n_active = n_total - inactive
    else:
        n_active = n_total

    # COMPOSITE dry-run (see EXPERIMENTS.md §Dry-run methodology):
    #  (1) scan-over-layers compile -> memory_analysis. The while loop
    #      forces per-period buffer reuse, which is what a real memory-
    #      aware (TPU) schedule does; an unrolled CPU schedule hoists
    #      remat buffers and wildly overstates peak.
    #  (2) unrolled 1-period and 2-period compiles -> exact per-period
    #      FLOPs/bytes/collectives deltas, extrapolated linearly to full
    #      depth (layers are structurally identical across periods;
    #      XLA's cost_analysis does not multiply while-body costs).
    n_periods = cfg.num_layers // cfg.period
    pcfg_scan = dataclasses.replace(pcfg, scan_layers=True)
    pcfg_unroll = dataclasses.replace(pcfg, scan_layers=False)

    compiled_scan, t_lower, t_compile = _lower_one(
        cfg, shape, pcfg_scan, opt_cfg, mesh
    )
    ma = compiled_scan.memory_analysis()
    if args.save_hlo:
        import gzip
        os.makedirs(os.path.dirname(args.save_hlo) or ".", exist_ok=True)
        with gzip.open(args.save_hlo, "wt") as f:
            f.write(compiled_scan.as_text())

    if multi_pod or args.scan:
        # pass/fail + memory cell: collectives from the scanned program
        # with loop multipliers; flops likewise (approximate, flagged).
        hlo = compiled_scan.as_text()
        ca = compiled_scan.cost_analysis()
        mults = find_loop_multipliers(hlo, n_periods)
        colls = parse_collectives(hlo, mults)
        flops = float(ca.get("flops", 0.0))
        bytes_acc = float(ca.get("bytes accessed", 0.0))
        accounting = "scan+loop-multipliers (approximate)"
    else:
        cfg1 = dataclasses.replace(cfg, num_layers=cfg.period)
        cfg2 = dataclasses.replace(cfg, num_layers=2 * cfg.period)
        c1, _, t1 = _lower_one(cfg1, shape, pcfg_unroll, opt_cfg, mesh)
        e1 = _extract(c1)
        del c1
        c2, _, t2 = _lower_one(cfg2, shape, pcfg_unroll, opt_cfg, mesh)
        e2 = _extract(c2)
        if args.save_hlo:
            import gzip
            with gzip.open(args.save_hlo + ".2p.gz", "wt") as f:
                f.write(e2["hlo"])
        del c2
        t_compile += t1 + t2
        flops = e1["flops"] + (n_periods - 1) * (e2["flops"] - e1["flops"])
        bytes_acc = e1["bytes"] + (n_periods - 1) * (e2["bytes"] - e1["bytes"])
        colls = {}
        kinds = set(e1["colls"]) | set(e2["colls"])
        for kind in kinds:
            b1 = e1["colls"].get(kind, {"bytes": 0, "count": 0})
            b2 = e2["colls"].get(kind, {"bytes": 0, "count": 0})
            colls[kind] = {
                "bytes": b1["bytes"] + (n_periods - 1) * (b2["bytes"] - b1["bytes"]),
                "count": b1["count"] + (n_periods - 1) * (b2["count"] - b1["count"]),
            }
        accounting = "unrolled 1p/2p extrapolation (exact)"

    coll_bytes = sum(v["bytes"] for v in colls.values())

    # XLA:CPU float-normalises bf16 to f32 (no native bf16 kernels), so
    # byte-denominated terms are ~2x a TPU compile for bf16 programs. We
    # report raw AND bf16-corrected (x0.5) terms; flops are dtype-exact.
    bf16_corr = 0.5 if cfg.dtype == "bfloat16" else 1.0

    # Kernel-true HBM correction: the XLA 'blocked' stand-in materialises a
    # (nblk, D, F_loc) weight-tile array per expert-specific matmul; the
    # Pallas ESMM/ESFK kernels stream each expert's slab through VMEM once
    # (sorted layout => revisit-cached). Subtract the stand-in's extra tile
    # traffic so t_memory reflects the kernel the system actually ships.
    moe_tile_extra = 0.0
    if cfg.moe is not None and pcfg.mode != "ep":
        axes_map = pcfg.axes(mesh)
        tp_size = mesh.shape.get("model", 1) if axes_map["tp"] else 1
        dp_size = n_chips // max(tp_size, 1)
        tok_island = shape.global_batch * (
            shape.seq_len if shape.kind != "decode" else 1
        ) // max(dp_size, 1)
        rows = tok_island * cfg.moe.top_k + cfg.moe.num_experts * (pcfg.blk - 1)
        nblk = max(rows // pcfg.blk, 1)
        f_loc = cfg.moe.d_ff // (
            tp_size if pcfg.mode in ("hybrid", "model_centric") else 1
        )
        n_moe = sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))
        n_mats = 3 if cfg.glu else 2
        tile = cfg.d_model * max(f_loc, 1) * 4  # f32 on the CPU backend
        per_esmm = (nblk - cfg.moe.num_experts) * tile
        # fwd(+remat refwd) esmm tile gathers + dW per-block outputs (rw)
        fwd_passes = n_mats * (2 if shape.kind == "train" else 1)
        dw_passes = 2 * n_mats if shape.kind == "train" else 0
        moe_tile_extra = n_moe * per_esmm * (fwd_passes + dw_passes)

    # Attention-transient correction: the pure-XLA online-softmax stand-in
    # materialises the (q_chunk x kv_block) logits/probability tensors in
    # HBM between the two dots of every (chunk, block) pair — a flash
    # kernel keeps them in VMEM. Estimated at 14 logits-sized tensor
    # traversals per pair (fwd 4: logits w+r, p w+r; remat re-fwd 4;
    # bwd 6: p r, dp w+r, dlogits w+r, read for dq/dk), f32 on this
    # backend. Subtracted for kernel-true t_memory.
    attn_extra = 0.0
    if shape.kind != "decode":
        axes_map = pcfg.axes(mesh)
        tp_size = mesh.shape.get("model", 1) if axes_map["tp"] else 1
        dp_size = n_chips // max(tp_size, 1)
        b_loc = max(shape.global_batch // max(dp_size, 1), 1)
        s = shape.seq_len
        n_attn = sum(
            cfg.layer_kind(i) == "attn" for i in range(cfg.num_layers)
        )
        heads_ok = (cfg.num_heads % tp_size == 0
                    and cfg.num_kv_heads % tp_size == 0)
        bk = min(2048, s)
        if heads_ok:
            cs = min(2048, s)
            nch = s // cs
            hq_loc = max(cfg.num_heads // tp_size, 1)
            pairs = sum(
                -(-((c + 1) * cs) // bk) for c in range(nch)
            )  # triangular (chunk, kv-block) pair count
            logits_unit = b_loc * cs * hq_loc * bk * 4
            per_layer = pairs * logits_unit
        else:
            cs_loc = s // tp_size  # queries stay seq-sharded
            pairs_rows = s // bk
            logits_unit = b_loc * cs_loc * cfg.num_heads * bk * 4
            per_layer = pairs_rows * logits_unit
        passes = 14 if shape.kind == "train" else 4
        attn_extra = n_attn * per_layer * passes

    t_comp = flops / PEAK_FLOPS
    t_mem = bytes_acc * bf16_corr / HBM_BW
    t_mem_kernel = (
        max(bytes_acc - moe_tile_extra - attn_extra, 0.0) * bf16_corr / HBM_BW
    )
    t_coll = coll_bytes * bf16_corr / LINK_BW

    # MODEL_FLOPS convention from the assignment (per-chip share).
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf_mult = 6 if shape.kind == "train" else 2
    model_flops = mf_mult * n_active * tokens / n_chips

    dom = max(
        (("compute", t_comp), ("memory", t_mem_kernel),
         ("collective", t_coll)),
        key=lambda kv: kv[1],
    )[0]

    return {
        "status": "ok",
        "arch": canon,
        "shape": shape_name,
        "mesh": dict(mesh.shape),
        "chips": n_chips,
        "mode": pcfg.mode,
        "schedule": pcfg.collective_schedule,
        "blk": pcfg.blk,
        "params_total": int(n_total),
        "params_active": int(n_active),
        "opt_state_dtype": opt_cfg.state_dtype,
        "master_fp32": opt_cfg.master_fp32,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "code_bytes": ma.generated_code_size_in_bytes,
            "peak_per_device": ma.argument_size_in_bytes + ma.temp_size_in_bytes,
        },
        "accounting": accounting,
        "bf16_byte_correction": bf16_corr,
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll_bytes,
        "collectives": colls,
        "roofline": {
            "t_compute_s": t_comp,
            "t_memory_s": t_mem,
            "t_memory_kernel_s": t_mem_kernel,
            "t_collective_s": t_coll,
            "dominant": dom,
            "model_flops_per_device": model_flops,
            "useful_flops_fraction": model_flops / flops if flops else None,
        },
    }


def main():
    """CLI: dry-run one cell and print/append its JSON report row."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--devices", type=int, default=None,
                    help="debug: smaller fake-device pool")
    ap.add_argument("--mesh-shape", default=None,
                    help="debug: e.g. '4,2' or '2,2,2'")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "hybrid", "model_centric",
                             "data_centric", "ep"])
    ap.add_argument("--schedule", default="ag_rs", choices=["ag_rs", "ag_ar"])
    ap.add_argument("--cache-policy", default="shared_cache",
                    choices=["shared_cache", "janus", "dots"])
    ap.add_argument("--scan", action="store_true",
                    help="scan layers instead of unrolling (smaller HLO, "
                         "approximate flop accounting)")
    ap.add_argument("--layers-override", type=int, default=None,
                    help="debug: truncate depth (rounded to a period)")
    ap.add_argument("--save-hlo", default=None,
                    help="gzip the optimized HLO here (perf analysis)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()

    result = run_cell(args.arch, args.shape, args.multi_pod, args)
    blob = json.dumps(result, indent=1, default=str)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(blob)
    print(blob)
    if result["status"] == "ok":
        print(f"\nDRYRUN OK {args.arch} {args.shape} "
              f"mesh={result['mesh']} dominant={result['roofline']['dominant']}")


if __name__ == "__main__":
    main()
