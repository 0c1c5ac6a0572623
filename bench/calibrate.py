#!/usr/bin/env python3
"""Readings that set a cell's correctness limits; not part of a run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds S] [--variants a,b]

For every seed it prints one JSON line with the numbers that decide
``correct``, read by the cell's driver (``drivers/<kind>.py``,
``readings``) from each variant it offers: ``program`` (the timed path at
the cell's own sizes), ``control`` (the plain reference one precision
below what the configuration states, put in the program's place) and the
planted faults the driver knows. ``--variants`` picks some of them (all by
default). Every variant is compared with the float32 reference. The last
line holds the largest reading of the program and the smallest of every
other variant. ``--rehearse`` runs at the tiny rehearsal sizes on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import generator, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="window of a serving cell's program reading")
    ap.add_argument("--variants", default="",
                    help="comma-separated variants (default: all)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload, "workload")
    entry = harness.find(bench["configs"], cell["config"], "config")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        harness.setup_jax()
    harness.devices_for(cell["chips"], rehearsal=args.rehearse)
    cfg, mod = harness.load_config(entry, rehearsal=args.rehearse)
    traffic = generator.load(cell["traffic"], rehearsal=args.rehearse)
    driver = harness.load_driver(traffic["kind"])
    variants = (args.variants.split(",") if args.variants
                else driver.VARIANTS)
    unknown = set(variants) - set(driver.VARIANTS)
    if unknown:
        raise ValueError(f"{sorted(unknown)} not in {driver.VARIANTS}")
    seeds = [int(s) for s in args.seeds.split(",")]
    worst: dict = {}
    for seed, out in driver.readings(cfg, mod, traffic, seeds,
                                     variants=variants,
                                     seconds=args.seconds):
        print(json.dumps({"seed": seed, **out}), flush=True)
        for variant, numbers in out.items():
            for k, v in numbers.items():
                pick = max if variant == "program" else min
                key = f"{variant}.{k}"
                worst[key] = pick(worst.get(key, v), v)
    print(json.dumps({"largest_program_smallest_other": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
