"""Shared benchmark utilities."""
from __future__ import annotations

import time

import jax
import numpy as np


def time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall-time per call in microseconds (blocks on results)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e6)


def time_pair(fn_a, fn_b, *args, rounds: int = 8) -> tuple[float, float, float]:
    """Interleaved A/B timing: (median_us_a, median_us_b, median a/b ratio).

    Alternating single calls makes the comparison robust to machine-load
    drift that would skew two back-to-back ``time_fn`` runs; the returned
    ratio is the median of the per-round a/b ratios (each round sees the
    same load), which is a steadier estimator than the ratio of medians.
    """
    for fn in (fn_a, fn_b, fn_a, fn_b):  # warm both (compile + caches)
        jax.block_until_ready(fn(*args))
    ta, tb = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a(*args))
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b(*args))
        tb.append(time.perf_counter() - t0)
    ratio = float(np.median(np.asarray(ta) / np.asarray(tb)))
    return float(np.median(ta) * 1e6), float(np.median(tb) * 1e6), ratio


def compiled_stats(fn, *abstract_args) -> dict:
    """Compile (AOT) and return memory/cost stats without executing."""
    lowered = jax.jit(fn).lower(*abstract_args)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    return {
        "peak_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "arg_bytes": ma.argument_size_in_bytes,
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }


#: Every emit() of the current process, in order — run.py serialises this to
#: BENCH_kernels.json so the per-PR perf trajectory is machine-readable.
RECORDS: list[dict] = []


def reset_records() -> None:
    RECORDS.clear()


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    """The run.py CSV contract: name,us_per_call,derived."""
    RECORDS.append(
        {"name": name, "us_per_call": float(us_per_call), "derived": derived}
    )
    print(f"{name},{us_per_call:.1f},{derived}")
