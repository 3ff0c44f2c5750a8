PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: test test-cov test-fast lint bench bench-smoke chaos-smoke deps deps-dev

# fixed fault-injection seed: chaos runs must be reproducible fault-for-fault
REPRO_FAULT_SEED ?= 7
export REPRO_FAULT_SEED

# committed coverage floor over the serving + kernel layers (a ratchet:
# raise it as coverage grows, never lower it to make a PR pass)
COV_FLOOR := 60

lint:  ## ruff bug-tier rules (config in pyproject.toml); CI runs this
	ruff check src tests

test:  ## tier-1 verify (no plugins needed; works in minimal containers)
	python -m pytest -x -q

test-cov:  ## CI variant: parallel via pytest-xdist, coverage-gated on serving/ + kernels/ + obs/ + core.graph/
	python -m pytest -x -q -n auto \
	    --cov=repro.serving --cov=repro.kernels --cov=repro.obs \
	    --cov=repro.core.graph \
	    --cov-report=term --cov-fail-under=$(COV_FLOOR)

test-fast:  ## compiler + kernel subset (quick signal while iterating)
	python -m pytest -x -q tests/test_graph_compiler.py tests/test_execution_plan.py tests/test_kernels.py

bench:
	python -m benchmarks.run

bench-smoke:  ## tiny-shape benchmark pass (CI-sized, no TPU; writes results/BENCH_*_smoke.json)
	python -m benchmarks.kernel_bench --smoke
	python -m benchmarks.table1_apps --smoke
	python -m benchmarks.serving_bench --smoke
	python -m benchmarks.robustness_bench --smoke
	python -m benchmarks.decode_bench --smoke
	python -m benchmarks.trajectory --check

chaos-smoke:  ## seeded fault-injection pass: chaos test suite + robustness smoke bench
	python -m pytest -x -q tests/test_robustness.py tests/test_state_isolation.py
	python -m benchmarks.robustness_bench --smoke
	python -m benchmarks.trajectory --check

deps:
	pip install -r requirements.txt

deps-dev:
	pip install -r requirements-dev.txt
