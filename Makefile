PYTHON ?= python

.PHONY: check test test-slow lint bench-paged bench-latency bench-smoke \
        bench-check serve docs-check

# lint is CI-gated separately (requires ruff; not in requirements.txt)
check: test docs-check bench-check

test:
	PYTHONPATH=src $(PYTHON) -m pytest -x -q

# chaos failover drills + deep property sweeps (non-blocking CI job)
test-slow:
	PYTHONPATH=src $(PYTHON) -m pytest -q -m slow --runslow

lint:
	$(PYTHON) -m ruff check .

docs-check:
	$(PYTHON) tools/check_docs.py

bench-paged:
	PYTHONPATH=src $(PYTHON) -m benchmarks.bench_kernels
	PYTHONPATH=src $(PYTHON) -m benchmarks.bench_overhead

# MTTR / TTFT / goodput under an injected failure, kevlarflow vs standard,
# plus the colocated-vs-disaggregated no-failure TTFT pair and the
# 12-instance fleet scenario matrix (incl. the shard_degraded cell:
# single-shard degraded serving vs whole-instance failover)
bench-latency:
	PYTHONPATH=src $(PYTHON) -m benchmarks.bench_latency
	PYTHONPATH=src $(PYTHON) -m benchmarks.bench_latency --disagg
	PYTHONPATH=src $(PYTHON) -m benchmarks.bench_failure --fleet --shard-faults

# CI smoke: regenerate bench output in fast modes, then schema-check it
bench-smoke:
	PYTHONPATH=src $(PYTHON) -m benchmarks.bench_latency --tiny
	PYTHONPATH=src $(PYTHON) -m benchmarks.bench_latency --tiny --disagg
	PYTHONPATH=src $(PYTHON) -m benchmarks.bench_failure --fleet --tiny --shard-faults
	PYTHONPATH=src $(PYTHON) -m benchmarks.bench_overhead --fast
	$(MAKE) bench-check

bench-check:
	$(PYTHON) tools/check_bench.py

serve:
	PYTHONPATH=src $(PYTHON) -m repro.serving.server --reduced
