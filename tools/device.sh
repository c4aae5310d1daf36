#!/bin/sh
# Device-plane suite on the VIRTUAL 8-device CPU mesh
# (XLA_FLAGS=--xla_force_host_platform_device_count=8). Tests always force
# the CPU: the chip is checked by `python chip_smoke.py` (one process per
# chip), never by this suite.
#
# The virtual mesh is probed FIRST with a hard deadline: a probe that
# fails or hangs exits non-zero — the suite did not run, and that is
# never a pass.
#
#   tools/device.sh                      # full device suite
#   tools/device.sh fleet                # fleet-batched serving suite only
#   tools/device.sh warmup               # pre-compile fleet kernels into
#                                        # the persistent compile cache
#                                        # (JAX_COMPILATION_CACHE_DIR, else
#                                        # .jax_compile_cache/) so the next
#                                        # serving restart starts warm
#   tools/device.sh tests/test_x.py::t   # specific tests (lint smoke)
#   VMT_DEVICE_PROBE_TIMEOUT_S=30 tools/device.sh
set -eu
cd "$(dirname "$0")/.."
TIMEOUT="${VMT_DEVICE_PROBE_TIMEOUT_S:-120}"
if ! env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        timeout -k 5 "$TIMEOUT" python -c "
import jax
jax.config.update('jax_platforms', 'cpu')
n = len(jax.devices())
assert n >= 8, f'only {n} virtual devices came up'
print(f'device.sh probe OK: {n} virtual cpu devices')
"; then
    echo "device.sh: FAILED - virtual-mesh probe failed or hung" \
         "(>${TIMEOUT}s); the device suite DID NOT RUN." >&2
    exit 1
fi
if [ "${1:-}" = "warmup" ]; then
    shift
    exec env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}" \
        JAX_ENABLE_X64="${JAX_ENABLE_X64:-1}" \
        python -m victoriametrics_tpu.devtools.compile_cache_smoke \
        --warmup "$@"
fi
if [ "${1:-}" = "fleet" ]; then
    shift
    set -- tests/test_device_fleet.py "$@"
fi
if [ "$#" -eq 0 ]; then
    set -- tests/test_device_residency.py tests/test_exec_query_mesh.py \
           tests/test_rolling_tile.py tests/test_served_device_path.py \
           tests/test_device_rollup.py tests/test_f32_tiles.py \
           tests/test_device_fleet.py
fi
exec env JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider "$@"
