#!/usr/bin/env bash
# CI entry point: deps -> tier-1 and multihost tests (CPU, Pallas interpret)
# -> chaos -> obs-check -> docs-check. Mirrors what the Makefile targets of
# the same names run locally. Performance is measured on the chip by
# bench/run.py, not here.
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

make docs-check
