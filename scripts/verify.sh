#!/usr/bin/env bash
# Tier-1 verify: the repo's test suite, then the perf smoke CI runs.
# pyproject.toml sets pythonpath=src, so no PYTHONPATH export is needed for
# pytest — this script exists so `scripts/verify.sh` is the one canonical
# spelling (extra pytest args pass through, e.g.
# `scripts/verify.sh -k relagg`).
#
# VERIFY_BENCH=0 skips the perf smoke (tests only).
set -euo pipefail
cd "$(dirname "$0")/.."
python -m pytest -x -q "$@"

# mirrors the CI sharded-8dev job: sharded parity tests + perf smoke on a
# forced 8-device CPU mesh (VERIFY_SHARDED=0 skips)
if [ "${VERIFY_SHARDED:-1}" != "0" ]; then
  echo "--- sharded parity: pytest on a forced 8-device host mesh"
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_sharded_many.py \
      tests/test_conformance_oracle.py tests/test_execute_many.py \
      tests/test_fused.py tests/test_fuse_cse.py
fi

# multi-statement fusion: fused-drain parity + perf smoke (the in-bench
# asserts are the parity check; the speedup bar is host-aware — see the CI
# fused gate).  VERIFY_FUSED=0 skips.
if [ "${VERIFY_FUSED:-1}" != "0" ]; then
  echo "--- fused drain parity + perf smoke: benchmarks.run --quick --only fused"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.run --quick --only fused \
      --run-id verify-fused --json-dir /tmp
fi

# cursor-loop rewrite: interpreted-vs-rewritten parity (in-bench assert)
# plus the loop-to-scan perf smoke — the CI gate requires >= 20x at N=1024.
# VERIFY_CURSORLOOP=0 skips.
if [ "${VERIFY_CURSORLOOP:-1}" != "0" ]; then
  echo "--- cursor-loop parity + perf smoke: benchmarks.run --quick --only cursorloop"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.run --quick --only cursorloop \
      --run-id verify-cursorloop --json-dir /tmp
fi

# decorrelation: the decorrelation conformance oracle (fixed grid, plain
# + forced 8-device mesh for the sharded execute_many axis) plus the
# correlated-subquery perf smoke — the CI gate requires the rewritten
# plan >= 10x over the compiled per-row apply at N=1024 with three-way
# parity asserted in-bench.  VERIFY_DECORR=0 skips.
if [ "${VERIFY_DECORR:-1}" != "0" ]; then
  echo "--- decorrelation oracle: pytest tests/test_decorrelate.py"
  python -m pytest -q tests/test_decorrelate.py
  echo "--- decorrelation oracle (8-device mesh): sharded decorrelated drains"
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_decorrelate.py
  echo "--- decorrelation perf smoke: benchmarks.run --quick --only decorr"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.run --quick --only decorr \
      --run-id verify-decorr --json-dir /tmp
fi

# resilience: chaos smoke on a forced 8-device mesh (ladder, breakers,
# deadlines, chaos conformance oracle) + the ladder-overhead perf smoke —
# the CI gate requires fault-free overhead <= 1.05 with in-bench parity.
# VERIFY_RESILIENCE=0 skips.
if [ "${VERIFY_RESILIENCE:-1}" != "0" ]; then
  echo "--- chaos smoke: pytest tests/test_resilience.py on a forced 8-device host mesh"
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_resilience.py
  echo "--- resilience overhead + demotion smoke: benchmarks.run --quick --only resilience"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.run --quick --only resilience \
      --run-id verify-resilience --json-dir /tmp
fi

# cost routing: routed-vs-oracle parity tests (plain + forced 8-device
# mesh for the sharded/fused-sharding regressions) + the routing perf
# smoke — the CI gates hold routed within the host-aware bars and the
# cache-resident bookkeeping overhead <= 1.05.  VERIFY_ROUTING=0 skips.
if [ "${VERIFY_ROUTING:-1}" != "0" ]; then
  echo "--- cost routing: pytest tests/test_cost_routing.py"
  python -m pytest -q tests/test_cost_routing.py
  echo "--- cost routing (8-device mesh): routing + fused-sharding regressions"
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_cost_routing.py tests/test_fused.py
  echo "--- routing perf smoke: benchmarks.run --quick --only routing"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.run --quick --only routing \
      --run-id verify-routing --json-dir /tmp
fi

# persistent plan tier + fleet serving: store/key/session persistence
# tests, the fleet conformance oracle (plain + forced 8-device mesh for
# sharded-entry round-trips), and the cold-vs-warm startup smoke — the CI
# gate requires warm first-call >= 10x faster than cold with every
# statement served from the store.  VERIFY_PERSIST=0 skips.
if [ "${VERIFY_PERSIST:-1}" != "0" ]; then
  echo "--- persistent tier: pytest tests/test_persist.py tests/test_fleet.py"
  python -m pytest -q tests/test_persist.py tests/test_fleet.py
  echo "--- fleet oracle (8-device mesh): sharded persistent-entry round-trips"
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_persist.py tests/test_fleet.py
  echo "--- fleet startup + drain smoke: benchmarks.run --quick --only fleet"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.run --quick --only fleet \
      --run-id verify-fleet --json-dir /tmp
fi

if [ "${VERIFY_BENCH:-1}" != "0" ]; then
  echo "--- perf smoke: benchmarks.run --quick --only prepared,table4,execmany"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.run --quick --only prepared,table4,execmany \
      --run-id verify --json-dir /tmp
  echo "--- sharded perf smoke: benchmarks.run --quick --only shardmany (8 devices)"
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.run --quick --only shardmany \
      --run-id verify-sharded --json-dir /tmp
fi
