# Entry points for the tier-1 verification commands (see ROADMAP.md).
#   make test         — the tier-1 gate: full suite, stop at first failure
#   make test-fast    — the <1 min lane: deselects @pytest.mark.slow tests
#   make test-sharded — the fast lane on 8 SIMULATED host devices: the ring
#                       ppermute / agent-axis-sharded engine paths run with
#                       nshards > 1 (they skip on a 1-device run), including
#                       the 2-D (seed=2, agent=4) and (seed=4, agent=2)
#                       make_surf_mesh shapes of tests/test_mesh2d.py
# The benchmark is bench/ (see BENCHMARK.json): python3 bench/run.py.
PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: test test-fast test-sharded

test:
	$(PY) -m pytest -x -q

test-fast:
	$(PY) -m pytest -x -q -m "not slow"

test-sharded:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	REPRO_SHARDED_LANE=1 $(PY) -m pytest -x -q -m "not slow"
