#!/usr/bin/env bash
# The tier-1 verify gate — CI and humans run the IDENTICAL command
# (CPU-forced jax, `slow`-marked tests excluded, collection errors
# tolerated so one broken module can't hide the rest). Prints
# DOTS_PASSED=<n> (count of passing-test dots) and exits with pytest's
# status. The floor on that count is the driver's (PERF_LEDGER.jsonl).
set -o pipefail
cd "$(dirname "$0")/.."

# fast-fail static pass BEFORE the pytest budget: a syntax error or an
# obvious undefined name should cost seconds, not a timeout. pyflakes
# is optional in the image; compileall is stdlib.
python -m compileall -q reflow_tpu tests tools chip_smoke.py \
  || { echo "TIER1: compileall failed"; exit 2; }
if python -c "import pyflakes" 2>/dev/null; then
  python -m pyflakes reflow_tpu chip_smoke.py \
    || { echo "TIER1: pyflakes failed"; exit 2; }
fi
# reflow-lint: the project's own invariant checker (lock discipline,
# seam hygiene, metrics pairing, env-knob registry, exception policy).
# AST-only — seconds, no jax import. docs/guide.md has the rule catalog.
python tools/reflow_lint.py \
  || { echo "TIER1: reflow-lint found violations"; exit 2; }

# the driver's own command (/root/TESTS_LAST_RUN.json: six xdist workers
# by file, 1 470 s), less its junit count and its
# ALLOW_MULTIPLE_LIBTPU_LOAD (the compile-only tests load the TPU
# compiler in a fixture of their one file: one worker, one load)
log=$(mktemp "${TMPDIR:-/tmp}/tier1.XXXXXX.log")
trap 'rm -f "$log"' EXIT
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
  --dist loadfile -p no:randomly 2>&1 | tee "$log"
rc=${PIPESTATUS[0]}
dots=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$log" | tr -cd . | wc -c)
echo DOTS_PASSED=$dots

# the lockcheck re-run: the concurrent suites (serve/tier/failover:
# producers, pump pools, shippers, failover coordinator) with the
# runtime lock-order monitor armed. Every named_lock acquisition feeds
# the held-before graph; ANY cycle raises LockOrderError and fails the
# run. The static twin is the reflow-lint lock pass above; this catches
# the orders the AST can't see (callbacks, cross-module call chains).
if [ $rc -eq 0 ]; then
  REFLOW_LOCKCHECK=1 JAX_PLATFORMS=cpu timeout -k 10 600 \
    python -m pytest tests/test_serve.py tests/test_tier.py \
    tests/test_failover.py -q -m 'not slow' -p no:cacheprovider \
    || { echo "TIER1: lockcheck run failed"; rc=3; }
fi
exit $rc
