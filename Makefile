# Developer entry points. The repo runs from source: PYTHONPATH=src.
PY ?= python
export PYTHONPATH := src

.PHONY: test test-fast test-batched test-chaos test-traces test-hetero \
        bench bench-smoke bench-gate docs-lint docs-lint-fast check report \
        report-smoke report-paper examples-smoke service-smoke

# tests run on the CPU (Pallas in interpret mode): no test takes a chip
test test-fast test-batched test-chaos test-traces test-hetero: export JAX_PLATFORMS := cpu

test:            ## tier-1 verification (what CI gates on) — the full suite
	$(PY) -m pytest -x -q

test-fast:       ## tier-1 minus @pytest.mark.slow parity sweeps (~fast inner loop)
	$(PY) -m pytest -x -q -m "not slow"

test-batched:    ## lane-engine differential suite incl. slow parity sweeps (docs/batched.md)
	$(PY) -m pytest -x -q tests/test_batched.py tests/test_kernels.py

test-chaos:      ## fault-tolerant runtime: crash/hang/flaky recovery + bit-identical resume (docs/robustness.md)
	$(PY) -m pytest -x -q tests/test_runtime.py

test-traces:     ## trace-ingestion contract suite: adapters, streaming, windows (docs/traces.md)
	$(PY) -m pytest -x -q tests/test_traces.py

test-hetero:     ## heterogeneous-fabric differential suite incl. slow parity sweeps (docs/heterogeneous.md)
	$(PY) -m pytest -x -q tests/test_hetero.py

bench-smoke:     ## ~60s campaign smoke: v2-vs-v1 speedup, JCT identity, parallel path
	$(PY) -m benchmarks.bench_campaign

bench-json:      ## campaign + batched + hetero + scale + fairshare + report + service + traces benches -> BENCH_campaign.json (+ gate)
	$(PY) -m benchmarks.run --only campaign,batched,hetero,scale,fairshare,report,service,traces --json
	$(PY) scripts/bench_gate.py

bench-gate:      ## fail if the committed BENCH_campaign.json lost the 5x target
	$(PY) scripts/bench_gate.py

bench:           ## every paper table/figure benchmark
	$(PY) -m benchmarks.run

docs-lint:       ## README/docs stay honest against the code (incl. results drift)
	$(PY) scripts/docs_lint.py

report:          ## regenerate the committed docs/results.md gallery (smoke scale)
	$(PY) -m repro.launch.report --scale smoke

report-smoke:    ## fail if docs/results.md or smoke CSVs drift from a fresh run
	$(PY) -m repro.launch.report --scale smoke --check

report-paper:    ## full figure suite (v2 streaming, 2048-GPU sweep) -> reports/paper/
	$(PY) -m repro.launch.report --scale paper

examples-smoke:  ## examples compile + their repro.* imports resolve + fast ones run
	$(PY) scripts/examples_smoke.py

service-smoke:   ## scheduler daemon end-to-end: TCP session, quotas, what-if, log replay (docs/service.md)
	$(PY) scripts/service_smoke.py

# check runs docs-lint with --no-results: report-smoke already rebuilds the
# smoke figure suite and byte-compares the gallery, so the drift check runs
# exactly once per check (standalone `make docs-lint` keeps the full set)
check: docs-lint-fast bench-gate examples-smoke service-smoke report-smoke test-fast test-batched test-chaos test-traces test-hetero   ## lint + perf gate + fast tests (full tier-1: make test)

docs-lint-fast:
	$(PY) scripts/docs_lint.py --no-results
