"""Test config: force JAX onto a virtual 8-device CPU mesh so multi-chip
sharding paths are exercised without TPU hardware.

Tests ALWAYS run on the CPU (several xdist workers cannot share a chip);
the chip is checked by `python chip_smoke.py`.
"""

import os

os.environ.setdefault("JAX_ENABLE_X64", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "race: concurrency/race-detector tests "
        "(tools/race.sh runs these under VMT_RACETRACE=1)")
    config.addinivalue_line("markers", "slow: excluded from tier-1 (-m 'not slow')")
    config.addinivalue_line(
        "markers", "crash: kill -9 crash-recovery matrix "
        "(tools/chaos.sh runs these; the full matrix is also slow-marked)")
    config.addinivalue_line(
        "markers", "requires_native: needs the native codec library "
        "(libvmcodec.so); skipped cleanly on minimal containers without "
        "a C++ toolchain instead of erroring")


def pytest_collection_modifyitems(config, items):
    try:
        from victoriametrics_tpu import native
        have_native = native.available()
    except Exception:
        have_native = False
    if have_native:
        return
    skip = pytest.mark.skip(
        reason="native codec library unavailable (no g++ / libvmcodec.so)")
    for item in items:
        if "requires_native" in item.keywords:
            item.add_marker(skip)
