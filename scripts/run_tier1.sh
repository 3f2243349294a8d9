#!/usr/bin/env bash
# Canonical tier-1 invocation: the fast unit tier (tests/conftest.py implies
# -m "not slow").  Extra pytest args pass through, e.g.:
#
#   scripts/run_tier1.sh                          # fast tier, <60s
#   scripts/run_tier1.sh -m "slow or not slow"    # everything
#   scripts/run_tier1.sh -m slow                  # slow tier only
#
# Persistent compilation cache: set JAX_COMPILATION_CACHE_DIR=<dir>; JAX
# reads it at import, so subprocess tests (sharded parity) inherit it too.
set -euo pipefail
cd "$(dirname "$0")/.."
# --durations=15 surfaces the slowest tests so compile-bound regressions in
# the engine tiers are visible in every CI log, not just the weekly bench.
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" exec python -m pytest -x -q --durations=15 "$@"
