#!/usr/bin/env bash
# Tier-1 verify. The driver's own command is the gate (a builder finds it
# under `commands` in the last run's record, /root/TESTS_LAST_RUN.json);
# this script mirrors it — the concurrency lint, then the same pytest
# line on the virtual CPU mesh (tests/conftest.py pins 8 CPU devices),
# six xdist workers, a file a worker — and prints DOTS_PASSED=<n>: the
# passes counted from the junit file, from pytest's progress dots where
# there is none. Exit code is pytest's, or the lint's if pytest passed.
# The driver also exports ALLOW_MULTIPLE_LIBTPU_LOAD=1 in its sandbox; this
# script does not (on a machine with a chip that lock keeps two processes
# off one chip): export it yourself to mirror the driver, or the second of
# the two files that describe a TPU topology may skip on its worker.
#
# Env knobs:
#   TIER1_LOG      log path (default /tmp/_t1.log; the junit file is
#                  beside it, .xml for .log)
#   TIER1_TIMEOUT  whole-run timeout in seconds (default 1470, the
#                  driver's; exit code 124 means it cut the run)
#   TIER1_ARGS     extra pytest args, or the files to run instead of
#                  tests/ (e.g. "-k spec", "tests/test_train_resilience.py")

set -o pipefail
cd "$(dirname "$0")/.."
LOG="${TIER1_LOG:-/tmp/_t1.log}"
XML="${LOG%.log}.xml"
rm -f "$LOG" "$XML"
TARGET="tests/"
case " ${TIER1_ARGS:-} " in
    *" tests/"*) TARGET="" ;;
esac
# Concurrency lint (docs/CONCURRENCY.md): gates every PR alongside the
# tests — guarded-field/lock-order/blocking-while-locked over the
# threaded serving/telemetry modules plus the metric-name/journal-kind
# audits, baselined exceptions in deepspeed_tpu/analysis/baseline.toml.
python scripts/lint_concurrency.py 2>&1 | tee -a "$LOG"
lint_rc=${PIPESTATUS[0]}
# shellcheck disable=SC2086
timeout -k 10 "${TIER1_TIMEOUT:-1470}" env JAX_PLATFORMS=cpu \
    python -m pytest $TARGET -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile --junitxml="$XML" \
    -p no:randomly ${TIER1_ARGS:-} 2>&1 | tee -a "$LOG"
rc=${PIPESTATUS[0]}
if [ "$rc" -eq 0 ] && [ "$lint_rc" -ne 0 ]; then
    rc=$lint_rc
fi
if [ "$rc" -ne 0 ]; then
    # failure digest: the last 20 failed/errored test ids plus any
    # concurrency-lint findings, so a regression is diagnosable from
    # this log alone (no re-run needed)
    echo "=== FAILURE DIGEST (last 20 failed test ids) ==="
    grep -aE '^(FAILED|ERROR) ' "$LOG" | tail -20
    if [ "$lint_rc" -ne 0 ]; then
        echo "--- concurrency lint findings ---"
        grep -a '^LINT ' "$LOG" | tail -20
    fi
    echo "=== END DIGEST (full log: $LOG) ==="
fi
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' \
    "$XML" 2>/dev/null | head -n 1 \
    | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo "DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" \
    | tr -cd . | wc -c)}"
echo "WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' "$LOG" 2>/dev/null)"
exit "$rc"
