"""The seams of the device bring-up (ISSUE 22): one compile cache with one
name, a device init that fails loudly, a smoke that refuses the CPU, a
native library never older than its sources — and the served device path
itself, driven over HTTP on the CPU backend by chip_smoke.py's own phases
at a small size (the path no test drove before)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices
    env.update(kw)
    return env


# -- one compile cache ---------------------------------------------------

@pytest.mark.parametrize("env_dir", ["/somewhere/else", None],
                         ids=["env-set", "env-unset"])
def test_compile_cache_dir_resolution(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR set -> the code sets no directory (jax
    reads the variable itself); unset -> the one fixed, git-ignored path
    in the checkout, never one made from $HOME, a temp name or a pid."""
    import jax

    from victoriametrics_tpu.query import tpu_engine as te
    calls = {}
    monkeypatch.setattr(te, "_CACHE_DIR_SET", False)
    monkeypatch.setattr(jax.config, "update", calls.__setitem__)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    te.enable_compilation_cache()
    if env_dir is not None:
        assert "jax_compilation_cache_dir" not in calls
    else:
        assert calls["jax_compilation_cache_dir"] == \
            os.path.join(ROOT, ".jax_compile_cache") == te.COMPILE_CACHE_DIR
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_compile_cache/" in f.read().split()
    assert "jax_persistent_cache_min_compile_time_secs" in calls


# -- a native library never older than its sources -----------------------

@pytest.mark.parametrize("so_age,stale", [(-100, True), (+100, False),
                                          (None, True)],
                         ids=["older", "newer", "missing"])
def test_native_library_staleness(monkeypatch, tmp_path, so_age, stale):
    from victoriametrics_tpu import native
    so = tmp_path / "libvmcodec.so"
    if so_age is not None:
        so.write_bytes(b"")
        newest = max(os.path.getmtime(os.path.join(native._DIR, f))
                     for f in native._SOURCES)
        os.utime(so, (newest + so_age, newest + so_age))
    monkeypatch.setattr(native, "_SO", str(so))
    assert native._stale() is stale


# -- a device init that fails loudly -------------------------------------

def test_vmsingle_exits_nonzero_when_device_init_fails(tmp_path):
    """-search.tpuBackend with a backend JAX cannot give: the process
    exits non-zero within seconds; it never serves from the host."""
    p = subprocess.run(
        [sys.executable, "-m", "victoriametrics_tpu.apps.vmsingle",
         f"-storageDataPath={tmp_path}/d", "-httpListenAddr=127.0.0.1:0",
         "-search.tpuBackend"],
        env=_env(JAX_PLATFORMS="no_such_backend"), capture_output=True,
        text=True, timeout=60)
    assert p.returncode != 0
    assert "device engine could not start" in p.stderr
    assert "http server listening" not in p.stderr


def test_chip_smoke_refuses_the_cpu():
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=_env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr


# -- the served device path over HTTP, on the CPU backend ----------------

@pytest.mark.parametrize("n_devices,four_chips", [(1, False), (4, True)],
                         ids=["one-device", "four-device-mesh"])
def test_served_device_path_over_http(n_devices, four_chips):
    """chip_smoke.run at a small size in a process of its own (JAX's
    device count is fixed per process): vmsingle started the way main()
    does with -search.tpuBackend, data in over HTTP, the device routes
    asked over HTTP query_range and held to the host reference, and
    vm_tpu_kernel_duration_seconds ticking for the fused kernel."""
    p = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; "
         f"chip_smoke.run(1088, 240, 16, 7, {four_chips})"],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count="
                           f"{n_devices}"),
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    kernel = "sharded_rollup_aggregate" if four_chips \
        else "rollup_aggregate_tile"
    executed = [ln for ln in p.stdout.splitlines()
                if ln.startswith("kernels executed")]
    assert executed and kernel in executed[0], p.stdout[-3000:]
    assert "refresh 2: window_cache_hits +1" in p.stdout
