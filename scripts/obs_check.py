#!/usr/bin/env python
"""Observability artifact validator (`make obs-check`, DESIGN.md §12).

Drives the real train and paged-serve drivers end-to-end with every
pillar enabled (seeded, tiny configs), then validates the artifacts the
operator would scrape or load — not merely that the runs survived:

  prometheus   the text dump parses under the exposition-format grammar
               (# HELP/# TYPE headers, `name{labels} value` series,
               histogram `_bucket/_sum/_count` triples), and the router
               invariant holds per phase:
               sum(expert_tokens) == top_k * routed_tokens
  trace        the Chrome trace JSON loads, every event carries the
               required keys (name/ph/pid/tid/ts, dur for "X"), and the
               span union covers >= 95% of the traced wall window
  events       the JSONL event log parses line-by-line and every record
               carries a monotonic-clock stamp and a kind

Prints one PASS line per artifact; exits non-zero on the first failure.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SERIES_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'            # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'     # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r' [0-9eE.+-]+(\.[0-9]+)?$|^.* (\+Inf|-Inf|NaN)$')


def check_prometheus(path: str, *, expect_phases) -> None:
    families = {}
    with open(path) as fh:
        for ln, line in enumerate(fh, 1):
            line = line.rstrip("\n")
            if not line:
                continue
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                parts = line.split(" ", 3)
                assert len(parts) >= 3, f"{path}:{ln}: bad comment {line!r}"
                if parts[1] == "TYPE":
                    assert parts[3] in ("counter", "gauge", "histogram"), \
                        f"{path}:{ln}: bad kind {parts[3]!r}"
                    families[parts[2]] = parts[3]
                continue
            assert SERIES_RE.match(line), f"{path}:{ln}: bad series {line!r}"
    for name, kind in families.items():
        assert name.startswith("repro_"), f"unprefixed family {name}"
        if kind == "counter":
            assert name.endswith("_total"), f"counter w/o _total: {name}"

    # Router invariant: every phase's per-expert counts sum to
    # top_k * routed tokens, integer-exact.
    per_phase_experts: dict = {}
    per_phase_routed: dict = {}
    with open(path) as fh:
        for line in fh:
            m = re.match(r'^repro_router_expert_tokens_total'
                         r'\{phase="([^"]+)",expert="\d+"\} (\d+)$', line)
            if m:
                per_phase_experts[m.group(1)] = (
                    per_phase_experts.get(m.group(1), 0) + int(m.group(2)))
            m = re.match(r'^repro_router_routed_tokens_total'
                         r'\{phase="([^"]+)"\} (\d+)$', line)
            if m:
                per_phase_routed[m.group(1)] = int(m.group(2))
    for phase, top_k in expect_phases.items():
        assert phase in per_phase_experts, f"no expert counts for {phase}"
        got, routed = per_phase_experts[phase], per_phase_routed[phase]
        assert got == top_k * routed, (
            f"{phase}: sum(expert_tokens)={got} != "
            f"top_k*routed={top_k * routed}")
    print(f"PASS prometheus {os.path.basename(path)} "
          f"({len(families)} families, phases {sorted(expect_phases)})")


def check_trace(path: str, *, min_coverage: float = 0.95) -> None:
    from repro.obs.tracing import chrome_span_coverage
    with open(path) as fh:
        trace = json.load(fh)
    evs = trace["traceEvents"]
    assert evs, "empty trace"
    for e in evs:
        for key in ("name", "ph", "pid", "tid", "ts"):
            assert key in e, f"event missing {key}: {e}"
        assert e["ts"] >= 0
        assert e["ph"] in ("X", "i"), f"unexpected phase {e['ph']!r}"
        if e["ph"] == "X":
            assert e["dur"] >= 0
    cov = chrome_span_coverage(trace)
    assert cov >= min_coverage, f"span coverage {cov:.1%} < {min_coverage:.0%}"
    print(f"PASS trace {os.path.basename(path)} "
          f"({len(evs)} events, coverage {cov:.1%})")


def check_events(path: str) -> None:
    records = []
    with open(path) as fh:
        for ln, line in enumerate(fh, 1):
            rec = json.loads(line)
            assert "kind" in rec and "t" in rec, f"{path}:{ln}: {rec}"
            records.append(rec)
    print(f"PASS events {os.path.basename(path)} ({len(records)} records)")


def run_train(tmp: str) -> dict:
    prom = os.path.join(tmp, "train_prom.txt")
    trace = os.path.join(tmp, "train_trace.json")
    events = os.path.join(tmp, "train_events.jsonl")
    subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "mixtral-8x7b", "--smoke", "--steps", "4",
         "--ckpt-dir", os.path.join(tmp, "ckpt"),
         "--metrics", prom, "--metrics-interval", "2",
         "--trace-out", trace, "--events-out", events],
        check=True, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")})
    return {"prom": prom, "trace": trace, "events": events}


def run_serve(tmp: str) -> dict:
    from repro import obs
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.launch import serve
    from repro.models import lm
    from repro.parallel.sharding import ParallelConfig, split_tree
    import jax
    import numpy as np

    cfg = ModelConfig(
        name="obs-check-moe", family="moe",
        num_layers=2, d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
        d_ff=0, vocab_size=64, dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff=64),
    )
    pcfg = ParallelConfig(blk=8, collect_router_stats=True)
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    obs.configure(metrics=True, tracing=True, event_log=True, reset=True)
    srv = serve.PagedServer(
        cfg, pcfg, None, num_slots=2, page_size=4, num_pages=32,
        max_pages_per_slot=8, params=params, prefill_chunk=4)
    rng = np.random.default_rng(5)
    for i in range(4):
        srv.submit(serve.Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(3, 10))).astype(
                                    np.int32),
            max_new=int(rng.integers(2, 5)), out=[]))
    srv.run()
    prom = os.path.join(tmp, "serve_prom.txt")
    trace = os.path.join(tmp, "serve_trace.json")
    events = os.path.join(tmp, "serve_events.jsonl")
    if srv.router_drain is not None:
        srv.router_drain.flush()
    obs.registry.collect()
    obs.dump_prometheus(obs.registry, prom)
    obs.tracer.write(trace)
    obs.events.write_jsonl(events)
    obs.configure(metrics=False, tracing=False, event_log=False, reset=True)
    return {"prom": prom, "trace": trace, "events": events,
            "top_k": cfg.moe.top_k}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", action="store_true",
                    help="leave the artifacts in a printed tempdir")
    args = ap.parse_args()
    tmp = tempfile.mkdtemp(prefix="obs_check_")
    train = run_train(tmp)
    check_prometheus(train["prom"], expect_phases={"train": 2})
    check_trace(train["trace"])
    check_events(train["events"])
    srv = run_serve(tmp)
    check_prometheus(srv["prom"], expect_phases={"serve": srv["top_k"]})
    check_trace(srv["trace"])
    check_events(srv["events"])
    if args.keep:
        print(f"artifacts kept in {tmp}")
    else:
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
    print("obs-check: all artifacts valid")
    return 0


if __name__ == "__main__":
    sys.exit(main())
