#!/usr/bin/env python3
"""Find the highest arrival rate a serving cell sustains; not part of a run.

    python3 bench/sweep.py --workload <cell> --seed N --seconds S --of-knee 0.6,0.8,1.0,1.25

Warms the cell up as a run does, ticks 10 s at the mix's own rate, and
estimates the knee from that: the engine prefills one chunk per tick, so
it admits at most one request per (mean prefill chunks per request) x
(mean tick) seconds, and its slots hold at most ``slots`` requests of
(mean output) x (mean tick) seconds each. It then offers
each rate (the given multiples of that estimate, or ``--rates`` in
requests per second) for ``--seconds``, one after another on the same
server, and prints one JSON line per rate: arrivals, first tokens,
completions, requests waiting for their first token at the end, the mean
tick, the mean number of slots decoding, and the tails. A rate the server sustains ends with a short,
steady queue; above the knee the queue grows all through the segment.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import compare, generator, harness  # noqa: E402


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--of-knee", default="",
                    help="comma-separated multiples of the estimated knee")
    ap.add_argument("--rates", default="",
                    help="comma-separated rates in requests per second")
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
    if not hasattr(driver, "start"):
        raise ValueError(f"traffic kind {traffic['kind']!r} has no rate")

    _, stream, loop, warm = driver.start(cfg, mod, traffic, args.seed)
    chunk = cfg["serving"]["prefill_chunk"]
    chunks = float(sum(-(-int(p) // chunk) for p in stream.prompt_lens)
                   / len(stream.prompt_lens))
    rec = loop.window(min(args.seconds, 10.0))    # the tick, under load
    tick_s = rec["window_s"] / max(len(rec["ticks"]), 1)
    outputs = float(stream.output_lens.mean())
    knee = min(1.0 / (chunks * tick_s), traffic["slots"] / (outputs * tick_s))
    print(json.dumps({"setup_s": time.perf_counter() - t0,
                      "warm_ticks": warm, "chunks_per_request": chunks,
                      "outputs_per_request": outputs, "tick_s": tick_s,
                      "knee_estimate_per_s": knee}),
          flush=True)
    rates = ([float(r) for r in args.rates.split(",")] if args.rates else
             [knee * float(m) for m in args.of_knee.split(",")])
    for rate in rates:
        stream.rate = rate
        waiting0 = loop.waiting()
        arrived0 = loop.rid
        rec = loop.window(args.seconds)
        line = {"rate_per_s": rate, "of_knee": rate / knee,
                "arrivals": loop.rid - arrived0,
                "first_tokens": len(rec["ttft"]),
                "done": len(rec["done"]), "waiting_before": waiting0,
                "waiting_after": loop.waiting(),
                "tick_s": rec["window_s"] / max(len(rec["ticks"]), 1),
                "decoding_mean": sum(len(t["decode"]) for t in rec["ticks"])
                / max(len(rec["ticks"]), 1),
                "tokens_per_s": rec["tokens"] / rec["window_s"]}
        for name, xs in (("ttft", rec["ttft"]), ("tpot", rec["tpot"])):
            if xs:
                line[f"{name}_p50_ms"] = compare.percentile(xs, 50) * 1e3
                line[f"{name}_p90_ms"] = compare.percentile(xs, 90) * 1e3
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
