#!/usr/bin/env bash
# CI entry point: deps -> tier-1 tests (CPU, Pallas interpret) -> benchmark
# smoke -> docs-check. Mirrors what `make test/bench/docs-check` run locally.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m pip install -r requirements.txt

# Hygiene: compiled bytecode must never be tracked (it churns every commit
# and is machine-specific).
if git ls-files '*.pyc' '**/__pycache__/*' | grep -q .; then
    echo "ERROR: tracked bytecode files:" >&2
    git ls-files '*.pyc' '**/__pycache__/*' >&2
    exit 1
fi

# Tier-1 on CPU, where Pallas kernels run in interpret mode
# (repro.common.pallas_interpret_default).
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Two test tiers (tests/conftest.py markers): tier-1 fast in-process tests
# first for quick failure, then the multihost tier (subprocess fake-device
# meshes: hierarchical dispatch parity, SPMD hetero execution, elastic CLI).
make test-tier1
make test-multihost

# Tier-2 chaos scenarios (DESIGN.md §9): deterministic fault plans through
# the real drivers — checkpoint-fallback bit-exactness, serving
# retry/re-jit stream parity, elastic shrink on device dropout.
make chaos

# Observability artifact validation (DESIGN.md §12): real train + serve
# runs with metrics/tracing/event-log on; grammar- and invariant-checked.
make obs-check

# Benchmark smoke: every paper-table module must at least run its quick grid
# (JAX_PLATFORMS=cpu via the Makefile) and emit BENCH_kernels.json +
# BENCH_hetero.json + BENCH_serve.json + BENCH_quant.json (the hetero suite
# runs the Eq. 1/2 uneven splits for real and asserts proportional <= uniform
# under simulated skew; the serve suite runs the mixed-length workload
# through the dense and paged drivers and asserts paged uses less peak KV
# cache with no tokens/s regression, then the high-duplicate prefix
# workload and asserts prefix-cached TTFT < uncached at a real hit-rate,
# then the speculative suite on a batch-1 repetitive workload and asserts
# spec-on decode tokens/s > 1.5x spec-off with token-identical output;
# the quant suite asserts int8 fused-FFN
# bytes < bf16, the crossover shift, and the equal-HBM paged-KV admission
# gain), so the harness and the machine-readable perf trajectory can't
# bit-rot.
make bench

# Validate the JSON files against the README-documented schema and pin the
# executed heterogeneous + paged-vs-dense serving comparison rows.
make bench-check

make docs-check
