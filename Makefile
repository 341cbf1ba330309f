# Tier-1 verification, coverage and lint entry points (see ROADMAP.md).
# The benchmark is perfbench/run.py (BENCHMARK.json), run on the chip.

PYTHON ?= python
export PYTHONPATH := src:.:$(PYTHONPATH)

.PHONY: test test-fast test-cov lint deps

test:
	$(PYTHON) -m pytest -x -q

# fast lane: skip the slow jax/pallas kernel and end-to-end tests so the
# scan-path suite gives signal in minutes (CI runs this per push; the
# full suite stays the tier-1 gate and runs nightly)
test-fast:
	$(PYTHON) -m pytest -x -q -m "not slow"

# coverage lane: line coverage over the query-plan and format layers
# (the join/semi-join surface lives there).  The floor is the measured
# ~92% minus noise headroom — a PR that adds untested branches to those
# layers fails here
test-cov:
	$(PYTHON) -m pytest -q -m "not slow" \
		--cov=repro.dataset --cov=repro.aformat --cov=repro.kernels \
		--cov=repro.ingest --cov=repro.data \
		--cov-report=term-missing:skip-covered --cov-fail-under=85

# ruff config lives in ruff.toml (correctness rules everywhere; the
# format gate ratchets over files added after the lint lane landed)
lint:
	ruff check src tests
	ruff format --check src tests

deps:
	$(PYTHON) -m pip install -r requirements-dev.txt
