"""Per-device-program heterogeneous MoE execution (paper §4.4 run for real;
DESIGN.md §6).

Real heterogeneous fleets (the paper's 2080Ti+TITAN cases, HeterMoE's
asymmetric GPU pools) cannot run one SPMD program: different device classes
compile different code. The execution model is therefore one *program per
device*, each with shapes cut from the plan:

  data-centric  — device i's program takes its Eq. 1 token shard
                  (``token_counts[i]`` rows, padded up to ``token_quantum``
                  with a masked tail) against the full expert weights;
                  shard outputs concatenate back to the global batch.
  model-centric — device i's program takes all tokens against its Eq. 2
                  hidden slice (``hidden_splits[i]`` columns — a quantum
                  multiple by construction, so every tile is MXU-aligned and
                  the esffn/esmm grids are sized from the *local* h_i: no
                  device does redundant FLOPs); partial outputs sum.

This is the physical realisation of the uneven split; the SPMD islands
(``parallel.moe_parallel``) realise the same plan *logically* on a
homogeneous mesh via masking, which is what the replan loop retraces. The
two agree numerically (tier-1 asserts it).

``timed_step`` measures each device program's wall time and scales it by
the plan's relative latencies — a *simulated-skew mesh*: the kernels run
for real at the uneven shapes on this host, and device i's clock runs
``t_i/t_min`` slower. The synchronous step latency is the max (the
all-reduce barrier).
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import round_up
from repro.core import espec
from repro.core.hetero import HeteroPlan
from repro.core.reindex import build_reindex
from repro.core.routing import route


class HeteroStep(NamedTuple):
    """One executed uneven step: output + measured per-device seconds +
    the simulated-skew synchronous latency (max over devices)."""
    y: jax.Array
    device_times_s: tuple
    step_latency_s: float




def _ffn(x, ri, params, *, act, glu, impl):
    if glu:
        scales = None
        if "w_gate_scale" in params:
            scales = (params["w_gate_scale"], params["w_up_scale"],
                      params["w_down_scale"])
        return espec.moe_glu(
            x, ri, params["w_gate"], params["w_up"], params["w_down"],
            scales=scales, act=act, impl=impl,
        )
    scales = None
    if "w1_scale" in params:
        scales = (params["w1_scale"], params["w2_scale"])
    return espec.moe_mlp(
        x, ri, params["w1"], params.get("b1"), params["w2"],
        params.get("b2"), scales=scales, act=act, impl=impl,
    )


class HeteroExecutor:
    """Per-device jitted programs for one MoE FFN layer under a HeteroPlan.

    ``params`` is the espec-style dict ('router' + GLU or MLP weights with
    the FULL d_ff hidden — slicing happens here). ``mode`` picks which Eq.
    the devices execute: "data_centric" needs ``plan.token_counts``,
    "model_centric" needs ``plan.hidden_splits``.

    Precision-aware planning (``plan.expert_bits``, DESIGN.md §8): a
    device class marked 8 holds its expert-weight slice as block-wise
    int8 payloads + scales (``quant.core.quantize_ffn``) and runs the
    fused-dequant kernels — per-device-program execution is exactly where
    mixed per-class precision is expressible, since each class compiles
    its own program. Classes marked 16 keep the full-precision weights.
    """

    def __init__(
        self,
        params: dict,
        *,
        num_experts: int,
        top_k: int,
        act: str,
        glu: bool,
        plan: HeteroPlan,
        mode: str,
        blk: int = 128,
        impl: Optional[str] = None,
        quant_mode: str = "int8",
        quant_tile: int = 128,
    ):
        from repro.quant.core import quantize_ffn

        self.plan = plan
        self.mode = mode
        self.glu = glu
        t = np.asarray(plan.proxy_latencies, np.float64)
        self.skews = tuple(float(v) for v in t / t.min())
        splits = (plan.token_counts if mode == "data_centric"
                  else plan.hidden_splits)
        bits = plan.expert_bits or (16,) * len(splits or ())
        if splits is not None and len(bits) != len(splits):
            # expert_bits is validated against proxy_latencies at plan
            # construction, but the executed split may follow tp_latencies
            # (model-centric on a 2-D mesh) — refuse a silent mis-mapping.
            raise ValueError(
                f"expert_bits has {len(bits)} entries but the executed "
                f"{mode} split has {len(splits)} device programs"
            )
        # data-centric programs share the UNSLICED weights, so all 8-bit
        # classes can share one quantized copy (model-centric slices
        # differ per class and must quantize per slice).
        shared_q = (quantize_ffn(params, mode=quant_mode, tile=quant_tile)
                    if mode == "data_centric" and 8 in bits else None)

        def class_params(i, p_i):
            if bits[i] != 8:
                return p_i
            if shared_q is not None:
                return shared_q
            return quantize_ffn(p_i, mode=quant_mode, tile=quant_tile)

        def layer_fn(x, p, n_valid, n_rows):
            vm = None
            if n_valid != n_rows:
                vm = jnp.arange(n_rows, dtype=jnp.int32) < n_valid
            r = route(x, params["router"], top_k, valid_mask=vm)
            ri = build_reindex(r.expert_idx, r.gates, num_experts, blk)
            return _ffn(x, ri, p, act=act, glu=glu, impl=impl)

        # ONE jitted callable shared by every device program: devices whose
        # shapes coincide (the whole uniform arm, or any equal shares) hit
        # the same trace cache instead of compiling n identical programs.
        jit_fn = jax.jit(layer_fn, static_argnames=("n_valid", "n_rows"))

        self._programs = []  # [(jitted_fn, device_params, shard_meta)]
        if mode == "data_centric":
            if plan.token_counts is None:
                raise ValueError("data_centric needs plan.token_counts")
            q = plan.token_quantum
            off = 0
            for i, b_i in enumerate(plan.token_counts):
                rows = max(round_up(b_i, q), q)
                fn = functools.partial(jit_fn, n_valid=b_i, n_rows=rows)
                self._programs.append(
                    (fn, class_params(i, params), (off, b_i, rows)))
                off += b_i
        elif mode == "model_centric":
            if plan.hidden_splits is None:
                raise ValueError("model_centric needs plan.hidden_splits")
            off = 0
            for i, h_i in enumerate(plan.hidden_splits):
                sl = slice(off, off + h_i)
                if glu:
                    p_i = {
                        "w_gate": params["w_gate"][:, :, sl],
                        "w_up": params["w_up"][:, :, sl],
                        "w_down": params["w_down"][:, sl, :],
                    }
                else:
                    p_i = {
                        "w1": params["w1"][:, :, sl],
                        "b1": (params["b1"][:, sl]
                               if params.get("b1") is not None else None),
                        "w2": params["w2"][:, sl, :],
                        # partial-sum bias: device 0 only, like the island's
                        # _mask_rank0 (avoids an n_dev-fold bias).
                        "b2": (params.get("b2") if off == 0 else
                               (jnp.zeros_like(params["b2"])
                                if params.get("b2") is not None else None)),
                    }
                fn = functools.partial(jit_fn, n_valid=-1, n_rows=-1)
                self._programs.append(
                    (fn, class_params(i, p_i), (off, h_i, None)))
                off += h_i
        else:
            raise ValueError(mode)

    def device_param_bytes(self) -> tuple:
        """Per-device expert-weight HBM bytes (router excluded) — the
        memory claim of per-class precision (DESIGN.md §8): an int8 class
        holds ~half the bf16 bytes (~quarter of f32) plus its scales."""
        from repro.common import tree_bytes

        return tuple(
            tree_bytes({k: v for k, v in p.items() if k != "router"})
            for _, p, _ in self._programs
        )

    # -- execution ----------------------------------------------------------

    def _run_device(self, i: int, x: jax.Array):
        fn, p, meta = self._programs[i]
        if self.mode == "data_centric":
            off, b_i, rows = meta
            shard = x[off: off + b_i]
            if rows != b_i:
                shard = jnp.concatenate(
                    [shard, jnp.zeros((rows - b_i, x.shape[1]), x.dtype)]
                )
            return fn(shard, p)
        return fn(x, p)

    def _combine(self, outs) -> jax.Array:
        """Merge per-device outputs: Eq. 1 shards concatenate (dropping each
        shard's quantum-pad tail), Eq. 2 partials sum over the hidden."""
        if self.mode == "data_centric":
            outs = [o[: meta[1]]
                    for o, (_, _, meta) in zip(outs, self._programs)]
            return jnp.concatenate(outs, axis=0)
        y = outs[0]
        for o in outs[1:]:
            y = y + o
        return y

    def __call__(self, x: jax.Array) -> jax.Array:
        """Execute the uneven step (no timing): x (N, D) -> y (N, D)."""
        return self._combine(
            [self._run_device(i, x) for i in range(len(self._programs))]
        )

    def timed_step(self, x: jax.Array, *, rounds: int = 5,
                   warmup: bool = True) -> HeteroStep:
        """Run + measure each device program; apply the simulated skew.

        Device i's best (min-over-rounds) wall time is scaled by
        ``t_i/t_min`` (that device is that much slower than this host's
        silicon); the synchronous step completes at the slowest device (the
        barrier). Min, not median: every program here runs on the SAME host
        serially, so load spikes are one-sided noise — the minimum is the
        faithful per-shape estimate the skew model should scale.

        ``warmup=False`` skips the untimed compile pass — for callers that
        interleave several timed_step calls (e.g. the A/B benchmark) and
        have already warmed every program.
        """
        n = len(self._programs)
        # warmup/compile every program first so rounds measure steady state
        outs = [None] * n
        if warmup:
            outs = [jax.block_until_ready(self._run_device(i, x))
                    for i in range(n)]
        times = [[] for _ in range(n)]
        for _ in range(rounds):
            for i in range(n):
                t0 = time.perf_counter()
                outs[i] = self._run_device(i, x)
                jax.block_until_ready(outs[i])
                times[i].append(time.perf_counter() - t0)
        best = tuple(float(np.min(t)) for t in times)
        step = max(m * s for m, s in zip(best, self.skews))
        return HeteroStep(y=self._combine(outs), device_times_s=best,
                          step_latency_s=step)
