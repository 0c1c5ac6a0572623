PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: test test-tier1 test-multihost docs-check chaos obs-check ci

test:
	$(PY) -m pytest -x -q

# The two test tiers (tests/conftest.py markers): tier1 = fast in-process
# tests; multihost = subprocess tests driving an
# --xla_force_host_platform_device_count fake-device mesh (hierarchical
# dispatch parity, SPMD hetero execution, elastic CLI). `make test` runs
# both in one invocation.
test-tier1:
	$(PY) -m pytest -x -q -m "not multihost"

test-multihost:
	$(PY) -m pytest -x -q -m multihost

# Tier-2 chaos suite (DESIGN.md §9): seeded fault plans driven through the
# real train/serve drivers, asserting bit-exact recovery — checkpoint
# fallback past corruption, serving abort/retry/re-jit parity, elastic
# shrink on device dropout.
chaos:
	JAX_PLATFORMS=cpu $(PY) scripts/chaos.py

# Observability artifacts (DESIGN.md §12): drive train + paged-serve with
# every pillar on and validate the Prometheus text grammar, the Chrome
# trace schema + >=95% span coverage, the JSONL event log, and the
# per-expert router invariant sum(expert_tokens) == top_k * routed.
obs-check:
	JAX_PLATFORMS=cpu $(PY) scripts/obs_check.py

# Every `DESIGN.md §N` citation in src/ must resolve to a `## §N` heading,
# and every public API in parallel/ + runtime/ + quant/ + launch/ must
# carry a docstring.
docs-check:
	$(PY) scripts/docs_check.py

ci:
	bash scripts/ci.sh
