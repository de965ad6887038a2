.PHONY: ci fast smoke lint serve-smoke train-smoke train-shard-smoke \
	update-smoke bench \
	bench-smoke bench-baseline

ci:            ## tier-1: full test suite (the per-PR bar; nightly in CI)
	scripts/ci.sh tier1

fast:          ## tier-1 minus `slow` (distributed / subprocess) tests
	scripts/ci.sh fast

smoke:         ## per-push gate: lint + import + collect + fast unit subset
	scripts/ci.sh smoke

lint:          ## forbidden-API checks only (missing packages, trainer syncs)
	scripts/ci.sh lint

serve-smoke:   ## serving end-to-end + gated serve_* ratios vs baseline
	scripts/ci.sh serve-smoke

train-smoke:   ## streamed walk→SGNS parity battery + gated train_* ratios
	scripts/ci.sh train-smoke

train-shard-smoke: ## sharded SGNS parity battery + gated train_shard_* ratios
	scripts/ci.sh train-shard-smoke

update-smoke:  ## delta/engine.update parity battery + gated update_* ratios
	scripts/ci.sh update-smoke

bench:         ## run the benchmark battery (CSV rows to stdout)
	PYTHONPATH=src python -m benchmarks.run

bench-smoke:   ## emit BENCH_smoke.json + compare ratios vs baseline (gate >2x)
	PYTHONPATH=src python -m benchmarks.bench_smoke BENCH_smoke.json
	python scripts/bench_compare.py BENCH_smoke.json \
	    benchmarks/baselines/BENCH_smoke.json --strict

bench-baseline: ## refresh the committed bench-smoke baseline
	PYTHONPATH=src python -m benchmarks.bench_smoke \
	    benchmarks/baselines/BENCH_smoke.json
