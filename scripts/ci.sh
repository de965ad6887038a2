#!/usr/bin/env bash
# Tiered CI entry point.
# Usage: scripts/ci.sh [tier1|fast|smoke|lint|serve-smoke|train-smoke|
#                       train-shard-smoke|update-smoke]
#   tier1 (default) — the full suite, the bar every PR must hold.
#                     Runtime varies 8 min - 2.5 h with machine load, so it
#                     runs nightly / on demand, NOT per push.
#   fast            — deselect `slow` (distributed/subprocess/bench-shaped)
#   smoke           — the per-push gate: forbidden-API lint, import check,
#                     collect-only, then a fast unit subset (minutes)
#   lint            — just the forbidden-API checks (missing packages,
#                     host round-trips in the streamed trainer)
#   serve-smoke     — serving end-to-end: serve_graph --smoke replays a Zipf
#                     trace, then bench_serve --smoke gates the serve_*
#                     ratios against the committed baseline
#   train-smoke     — streamed walk→SGNS training end-to-end: the train
#                     parity battery, then bench_train --smoke gates the
#                     train_* ratios against the committed baseline
#   train-shard-smoke — sharded SGNS end-to-end: the shard parity battery
#                     (incl. the 2-fake-device subprocess bit-identity
#                     tests), then bench_train --smoke re-gates the
#                     train_* ratios — including the ISSUE-10 acceptance
#                     asserts (bit-identical across shard counts, shard2/
#                     dense pairs/sec >= 1.5x) that run inside the bench
#   update-smoke    — incremental graph updates end-to-end: the delta /
#                     engine.update parity batteries, then bench_update
#                     --smoke gates the update_* ratios (and the ISSUE-9
#                     acceptance asserts) against the committed baseline
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Fast unit subset for the smoke tier: core graph/ingest/sampling math.
# Everything here runs in seconds; the heavyweight LM-lowering and
# multi-device subprocess suites stay in tier-1.
SMOKE_TESTS=(tests/test_graph.py tests/test_ingest.py tests/test_alias.py
             tests/test_transition.py)

lint() {
  # Forbidden APIs — environment quirks codified so they can't regress
  # (the container has no hypothesis and pip install is not permitted).
  local fail=0
  local paths=(src tests benchmarks examples scripts)

  if grep -rnE "^[[:space:]]*(import hypothesis|from hypothesis)" \
       "${paths[@]}" --include="*.py"; then
    echo "LINT FAIL: hypothesis is not installed in the CI container;" \
         "use seeded pytest.mark.parametrize sweeps instead" >&2
    fail=1
  fi

  # the streamed trainer's contract is "no host round-trips in the hot
  # path" (DESIGN.md §14/§16): device syncs in src/repro/train/ must be
  # per-round or terminal, and say so with a `# host-ok: ...` tag on the
  # line. block_until_ready has no legitimate use there at all.
  if grep -rn "\.block_until_ready()" src/repro/train/ --include="*.py"; then
    echo "LINT FAIL: block_until_ready in the streamed trainer (host" \
         "sync in the hot path); let dispatch run ahead instead" >&2
    fail=1
  fi
  if grep -rnE "\bnp\.asarray|\bnp\.ascontiguousarray|jax\.device_get" \
       src/repro/train/ --include="*.py" | grep -v "# host-ok"; then
    echo "LINT FAIL: host round-trip in src/repro/train/ without a" \
         "'# host-ok: <why>' tag (only per-round input staging and" \
         "terminal fetches are allowed in the streamed trainer)" >&2
    fail=1
  fi

  if [ "$fail" -ne 0 ]; then exit 1; fi
  echo "lint: forbidden-API checks passed"
}

target="${1:-tier1}"
case "$target" in
  tier1) exec python -m pytest -x -q --durations=10 ;;
  fast)  exec python -m pytest -x -q -m "not slow" --durations=10 ;;
  lint)  lint ;;
  smoke)
    lint
    echo "smoke: import check"
    python -c "import repro.engine, repro.data, repro.data.ingest, \
repro.data.deltas, repro.core.graph, repro.core.walk_distributed, \
repro.roofline.analysis, repro.serve, repro.train; print('imports OK')"
    echo "smoke: collect-only"
    python -m pytest -q --collect-only >/dev/null
    echo "smoke: fast unit subset"
    exec python -m pytest -x -q -m "not slow" --durations=10 \
      "${SMOKE_TESTS[@]}"
    ;;
  serve-smoke)
    echo "serve-smoke: end-to-end Zipf trace through the embedding service"
    python -m repro.launch.serve_graph --smoke
    echo "serve-smoke: deterministic serve_* ratios vs baseline"
    python -m benchmarks.bench_serve --smoke BENCH_smoke.json
    exec python scripts/bench_compare.py BENCH_smoke.json \
      benchmarks/baselines/BENCH_smoke.json --strict --only serve_
    ;;
  train-smoke)
    echo "train-smoke: streamed-vs-concat / fused-vs-jnp parity battery"
    python -m pytest -x -q tests/test_train.py
    echo "train-smoke: train_* ratios vs baseline"
    python -m benchmarks.bench_train --smoke BENCH_smoke.json
    exec python scripts/bench_compare.py BENCH_smoke.json \
      benchmarks/baselines/BENCH_smoke.json --strict --only train_
    ;;
  train-shard-smoke)
    lint
    echo "train-shard-smoke: sharded parity battery (2-device subprocess" \
         "bit-identity, numpy oracle, zero-retrace, alias parity)"
    python -m pytest -x -q tests/test_train_shard.py
    echo "train-shard-smoke: train_* ratios vs baseline (incl. the" \
         "shard2/dense >= 1.5x and bit-identity asserts in the bench)"
    python -m benchmarks.bench_train --smoke BENCH_smoke.json
    exec python scripts/bench_compare.py BENCH_smoke.json \
      benchmarks/baselines/BENCH_smoke.json --strict --only train_shard_
    ;;
  update-smoke)
    echo "update-smoke: delta ingestion + engine.update parity batteries"
    python -m pytest -x -q -m "not slow" tests/test_deltas.py \
      tests/test_update.py
    echo "update-smoke: update_* ratios vs baseline"
    python -m benchmarks.bench_update --smoke BENCH_smoke.json
    exec python scripts/bench_compare.py BENCH_smoke.json \
      benchmarks/baselines/BENCH_smoke.json --strict --only update_
    ;;
  *) echo "unknown target: $target" \
          "(want tier1|fast|smoke|lint|serve-smoke|train-smoke|" \
          "train-shard-smoke|update-smoke)" >&2
     exit 2 ;;
esac
