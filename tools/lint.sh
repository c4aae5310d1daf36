#!/bin/sh
# Canonical static-analysis entry point (tier-1 / CI): runs the project
# lint engine over the package. Exit codes:
#   0  clean against devtools/lint_baseline.txt
#   1  new findings (not grandfathered, not inline-disabled)
#   3  baseline staleness: grandfathered entries that no longer fire —
#      slack in the ratchet; regenerate with --update-baseline
# Extra args are passed through, e.g.:
#   tools/lint.sh --update-baseline
#   tools/lint.sh --no-baseline victoriametrics_tpu/storage/
#
# After a clean lint, the flight-recorder overhead smoke check runs
# (devtools/flight_overhead.py): the always-on record path must stay
# under a per-event ns budget AND within VM_FLIGHT_SMOKE_PCT (default
# 2%) of VM_FLIGHTREC=0 on a serving-shaped workload — exit 1 on an
# overhead regression.  VMT_NO_FLIGHT_SMOKE=1 skips it (e.g. when
# iterating on lint findings only).
#
# Then a single-crashpoint smoke (one armed kill -9 seam + clean-reopen
# check, ~3s): the crash-injection harness itself must not rot between
# full tools/chaos.sh runs.  VMT_NO_CRASH_SMOKE=1 skips it.
#
# And a device-residency smoke (tools/device.sh with the tier-1 guard
# test): the virtual 8-device mesh + resident-window upload guard must
# not rot between full device.sh runs; probe hang -> loud skip.
# VMT_NO_DEVICE_SMOKE=1 skips it.
set -eu
cd "$(dirname "$0")/.."
# --changed-only: lint just the .py files that differ from the merge
# base (VMT_CHANGED_BASE, default main) plus untracked ones — the fast
# inner loop while editing.  The call-graph passes (VMT012/VMT015/
# VMT016) still run — built over the WHOLE package, since they are
# interprocedural — but report only findings landing in the changed
# files (--scoped-program-passes); wireschema and the smokes stay
# full-gate-only (tools/check.sh).
if [ "${1:-}" = "--changed-only" ]; then
    shift
    base=$(git merge-base HEAD "${VMT_CHANGED_BASE:-main}" 2>/dev/null \
           || git rev-parse HEAD)
    changed=$( { git diff --name-only "$base" -- '*.py';
                 git ls-files --others --exclude-standard -- '*.py'; } \
               | sort -u)
    files=""
    for f in $changed; do
        [ -f "$f" ] && files="$files $f"
    done
    if [ -z "$files" ]; then
        echo "lint: no changed .py files vs $(git rev-parse --short "$base")"
        exit 0
    fi
    # shellcheck disable=SC2086
    exec python -m victoriametrics_tpu.devtools.lint \
        --scoped-program-passes $files "$@"
fi
if [ "$#" -eq 0 ]; then
    set -- victoriametrics_tpu/
fi
python -m victoriametrics_tpu.devtools.lint "$@"
if [ "${VMT_NO_FLIGHT_SMOKE:-0}" != "1" ]; then
    python -m victoriametrics_tpu.devtools.flight_overhead
fi
# Continuous-profiler overhead smoke (devtools/profile_overhead.py):
# the default-on sampling thread must stay within VM_PROFILE_SMOKE_PCT
# (default 2%) of profiler-stopped on a serving-shaped workload.
# VMT_NO_PROFILE_SMOKE=1 skips it.
if [ "${VMT_NO_PROFILE_SMOKE:-0}" != "1" ]; then
    python -m victoriametrics_tpu.devtools.profile_overhead
fi
# Materialized-stream fan-out smoke (devtools/matstream_overhead.py):
# one interval with N subscribers must cost ONE evaluation with flat
# samples-scanned and near-zero per-subscriber fan-out cost.
# VMT_NO_MATSTREAM_SMOKE=1 skips it.
if [ "${VMT_NO_MATSTREAM_SMOKE:-0}" != "1" ]; then
    env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python -m victoriametrics_tpu.devtools.matstream_overhead
fi
# Self-monitoring plane overhead smoke (devtools/selfscrape_overhead.py):
# one scrape+SLO-eval cycle against a real Storage must stay within
# VM_SELFSCRAPE_SMOKE_PCT (default 2%) duty cycle of the 15s interval.
# VMT_NO_SELFSCRAPE_SMOKE=1 skips it.
if [ "${VMT_NO_SELFSCRAPE_SMOKE:-0}" != "1" ]; then
    env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python -m victoriametrics_tpu.devtools.selfscrape_overhead
fi
# Elastic-cluster reshard smoke (devtools/reshard_smoke.py): a second
# vmstorage joins a 1-node cluster without a restart, rebalance moves
# real parts over migrateParts_v1 byte-exactly, and an RF=2 down node
# serves COMPLETE results through the explicit reroute path.  Skips
# itself (exit 0) when no zstd codec exists; VMT_NO_RESHARD_SMOKE=1
# skips it outright.
if [ "${VMT_NO_RESHARD_SMOKE:-0}" != "1" ]; then
    env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python -m victoriametrics_tpu.devtools.reshard_smoke
fi
# Downsample tier smoke (devtools/downsample_smoke.py): one re-rollup
# cycle against a real Storage; the 5m tier must serve a hinted
# long-range fetch with >=4x fewer samples and stay bit-exact vs the
# raw oracle.  VMT_NO_DOWNSAMPLE_SMOKE=1 skips it.
if [ "${VMT_NO_DOWNSAMPLE_SMOKE:-0}" != "1" ]; then
    env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python -m victoriametrics_tpu.devtools.downsample_smoke
fi
# Persistent compile-cache smoke (devtools/compile_cache_smoke.py): a
# second cold process must compile 0 kernels for a fleet bucket shape
# the first process warmed (jax's persistent cache, shared through
# JAX_COMPILATION_CACHE_DIR).  VMT_NO_COMPILE_CACHE_SMOKE=1 skips it.
if [ "${VMT_NO_COMPILE_CACHE_SMOKE:-0}" != "1" ]; then
    env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        python -m victoriametrics_tpu.devtools.compile_cache_smoke
fi
if [ "${VMT_NO_DEVICE_SMOKE:-0}" != "1" ]; then
    sh tools/device.sh \
        "tests/test_device_residency.py::test_refresh_uploads_only_tail_on_mesh"
fi
if [ "${VMT_NO_CRASH_SMOKE:-0}" != "1" ]; then
    exec env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
        "tests/test_crash_recovery.py::test_crashpoint_seam[part:finalize:pre_rename]" \
        -q -p no:cacheprovider
fi
