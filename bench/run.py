#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name through ``BENCHMARK.json`` (see bench/README.md). The last line on
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number of the correctness
comparison beside its limit. The checks are also the last lines on
standard error.

``--rehearse`` runs the cell at the tiny sizes its files give for a
rehearsal, on the CPU, and prints no metric: a CPU run is never a device
result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import generator, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload, "workload")
    entry = harness.find(bench["configs"], cell["config"], "config")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        harness.setup_jax()
    devices = harness.devices_for(cell["chips"], rehearsal=args.rehearse)
    cfg, mod = harness.load_config(entry, rehearsal=args.rehearse)
    traffic = generator.load(cell["traffic"], rehearsal=args.rehearse)
    limits = harness.load_limits(cell["name"], rehearsal=args.rehearse)
    driver = harness.load_driver(traffic["kind"])

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as trace_dir:
        out = driver.run(cell=cell, cfg=cfg, mod=mod, traffic=traffic,
                         seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), devices=devices,
                         limits=limits, t0=T0, trace_dir=trace_dir)
    correct = all(c.ok for c in out["checks"]) and out["failed"] == 0
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.rehearse:
        harness.emit({**result, "rehearsal": True, "device": out["device"]},
                     out["checks"])
        return 0
    ctx = out["context"]
    if args.trace:
        names = harness.per_layer_names(
            bench, cell["name"], set(harness.end_to_end_names(bench,
                                                               cell["name"])))
        result["metrics"] = harness.read_metrics(names, ctx)
        result["device"] = {**out["device"], "busy_s": ctx.trace.busy_s(),
                            "window_s": ctx.trace.window_s}
        result["breakdown"] = ctx.trace.breakdown()
    else:
        names = harness.end_to_end_names(bench, cell["name"])
        result["metrics"] = {k: v for k, v in out["end_to_end"].items()
                             if k in names}
        result["device"] = out["device"]
    harness.emit(result, out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
