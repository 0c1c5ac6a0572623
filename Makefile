PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: test test-tier1 test-multihost bench bench-check docs-check chaos obs-check ci

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

bench:
	JAX_PLATFORMS=cpu $(PY) benchmarks/run.py --quick

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

# BENCH_*.json must match the README-documented schema, the executed
# heterogeneous comparison rows must be present, and the serving
# paged-vs-dense comparison must carry both sides of every claim.
bench-check:
	$(PY) scripts/validate_bench.py BENCH_kernels.json BENCH_hetero.json \
		BENCH_serve.json BENCH_quant.json BENCH_obs.json \
		--require hetero_exec/data_centric/uniform \
		--require hetero_exec/data_centric/proportional \
		--require hetero_exec/model_centric/uniform \
		--require hetero_exec/model_centric/proportional \
		--require serve/paged/tokens_per_s \
		--require serve/dense/tokens_per_s \
		--require serve/prefix/hit_rate \
		--require serve/spec/on/tokens_per_s \
		--require serve/spec/acceptance \
		--require quant/esffn/bytes \
		--require hetero/topology/flat \
		--lt hetero/topology/hier:hetero/topology/flat \
		--lt serve/paged/kv_cache_bytes:serve/dense/kv_cache_bytes \
		--lt serve/prefix/ttft/cached:serve/prefix/ttft/uncached \
		--lt serve/spec/on/tokens_per_s:serve/spec/off/tokens_per_s \
		--lt quant/esffn/bytes/int8:quant/esffn/bytes/bf16 \
		--lt quant/crossover/tokens/int8:quant/crossover/tokens/bf16 \
		--lt quant/kv/admitted/fp:quant/kv/admitted/int8 \
		--require obs/overhead/step_ratio \
		--require obs/overhead/limit \
		--lt obs/overhead/step_ratio:obs/overhead/limit

ci:
	bash scripts/ci.sh
