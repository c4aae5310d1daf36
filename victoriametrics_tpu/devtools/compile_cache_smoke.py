"""Persistent compile-cache smoke (tools/lint.sh + tools/check.sh gate):
a second cold process must compile ZERO kernels for a bucket shape the
first process warmed.  Without this gate a jax upgrade or a config drift
(min-compile-time threshold, cache-key salt) silently reverts every
restart to paying the full fused-kernel compile storm.

Two child processes share one ``JAX_COMPILATION_CACHE_DIR`` (jax's own
persistent compilation cache — the one cache there is).  Each child
compiles ONE small fleet bucket through the real mesh path
(parallel.mesh.cached_fleet_rollup_aggregate) and reports the
backend-compile / cache-hit counters.  The warm child must report
0 compiles and >= 1 hits.
``VMT_NO_COMPILE_CACHE_SMOKE=1`` skips from tools/lint.sh / check.sh.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile


def _child() -> int:
    import jax
    import numpy as np

    from ..ops.device_rollup import TS_PAD, normalized_cfg
    from ..ops.rollup_np import RollupConfig
    from ..parallel.mesh import cached_fleet_rollup_aggregate, make_fleet_mesh
    from ..query import tpu_engine as te

    te.enable_compilation_cache()
    # the smoke kernel is tiny; cache it regardless of compile speed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from ..query.fleet import bucket_up

    # the bucket axis shards across the mesh, so B must land on the same
    # device-aware rung query.fleet uses (a caller-inherited XLA_FLAGS
    # device count > 2 would otherwise make B=2 unshardable)
    B = bucket_up(2, len(jax.devices()))
    S, N, G, T = 8, 64, 4, 10
    step = 60_000
    cfg = normalized_cfg("rate", RollupConfig(0, (T - 1) * step, step,
                                              300_000))
    rng = np.random.default_rng(7)
    ts = np.full((B, S, N), TS_PAD, np.int32)
    vals = np.zeros((B, S, N))
    counts = np.full((B, S), N // 2, np.int32)
    for b in range(B):
        for s in range(S):
            ts[b, s, :N // 2] = np.sort(
                rng.integers(-300_000, (T - 1) * step, N // 2)).astype(
                    np.int32)
            vals[b, s, :N // 2] = np.cumsum(rng.integers(0, 20, N // 2))
    gids = (np.arange(S, dtype=np.int32) % G)[None, :].repeat(B, 0)
    # sum / max alternating: aggr codes are data, one program serves both
    aggr = np.resize(np.array([0, 4], np.int32), B)
    shift = np.zeros(B, np.int32)
    min_ts = np.full(B, -(2**31) + 1, np.int32)
    v0 = np.zeros((B, S))

    mesh = make_fleet_mesh(jax.devices())
    fn = cached_fleet_rollup_aggregate(mesh, "rate", cfg, G)
    out = np.asarray(fn(ts, vals, counts, gids, aggr, shift, min_ts, v0))
    assert out.shape == (B, G, T), out.shape
    assert np.isfinite(out).any(), "fleet smoke kernel produced no values"
    print(json.dumps({"compiles": te.backend_compiles(),
                      "hits": te.compile_cache_hits()}))
    return 0


def _warmup() -> int:
    """``tools/device.sh warmup``: pre-compile the fleet kernel for the
    deployment's common bucket shapes into the persistent cache
    (query.tpu_engine.enable_compilation_cache), so the serving process after the next
    restart deserializes instead of paying the cold compile storm.
    ``VM_WARMUP_FUNCS`` (default rate), ``VM_WARMUP_SHAPE`` ("B,S,N,T,G"
    ladder rungs), ``VM_WARMUP_STEP_MS`` and ``VM_WARMUP_WINDOW_MS``
    pick the shapes — they must land on the SAME rungs query.fleet
    derives or the warmed entries are dead weight."""
    import jax
    import numpy as np

    from ..ops.device_rollup import TS_PAD, normalized_cfg
    from ..ops.rollup_np import RollupConfig
    from ..parallel.mesh import cached_fleet_rollup_aggregate, make_fleet_mesh
    from ..query import fleet as fleetmod
    from ..query import tpu_engine as te

    te.enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    funcs = os.environ.get("VM_WARMUP_FUNCS", "rate").split(",")
    shape = [int(x) for x in os.environ.get(
        "VM_WARMUP_SHAPE", "8,512,384,24,64").split(",")]
    B, S, N, T, G = (fleetmod.bucket_up(shape[0], len(jax.devices())),
                     fleetmod.bucket_up(shape[1]),
                     fleetmod.bucket_up(shape[2], 64),
                     fleetmod.bucket_up(shape[3]),
                     fleetmod.bucket_up(shape[4]))
    step = int(os.environ.get("VM_WARMUP_STEP_MS", "60000"))
    window = int(os.environ.get("VM_WARMUP_WINDOW_MS", "300000"))
    mesh = make_fleet_mesh(jax.devices())
    ts = np.full((B, S, N), TS_PAD, np.int32)
    vals = np.zeros((B, S, N))
    counts = np.zeros((B, S), np.int32)
    gids = np.zeros((B, S), np.int32)
    aggr = np.zeros(B, np.int32)
    shift = np.zeros(B, np.int32)
    min_ts = np.full(B, -(2**31) + 1, np.int32)
    v0 = np.zeros((B, S))
    for func in funcs:
        cfg = normalized_cfg(func, RollupConfig(0, (T - 1) * step, step,
                                                window))
        fn = cached_fleet_rollup_aggregate(mesh, func, cfg, G)
        np.asarray(fn(ts, vals, counts, gids, aggr, shift, min_ts, v0))
    print(f"compile-cache warmup: {len(funcs)} func(s) x "
          f"[B={B},S={S},N={N},T={T},G={G}] -> "
          f"{te.backend_compiles()} compiled, "
          f"{te.compile_cache_hits()} already cached")
    return 0


def _spawn(cache_dir: str) -> dict:
    env = dict(os.environ)
    env.update(JAX_COMPILATION_CACHE_DIR=cache_dir,
               JAX_PLATFORMS=env.get("JAX_PLATFORMS", "cpu"),
               JAX_ENABLE_X64="1")
    p = subprocess.run(
        [sys.executable, "-m",
         "victoriametrics_tpu.devtools.compile_cache_smoke", "--child"],
        env=env, capture_output=True, text=True, timeout=600)
    if p.returncode != 0:
        raise RuntimeError(f"child failed rc={p.returncode}:\n"
                           f"{p.stdout}\n{p.stderr}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def main() -> int:
    if "--child" in sys.argv:
        return _child()
    if "--warmup" in sys.argv:
        return _warmup()
    tmp = tempfile.mkdtemp(prefix="ccache-smoke-")
    try:
        cold = _spawn(tmp)
        warm = _spawn(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if cold["compiles"] < 1:
        failure = (f"cold child reported {cold['compiles']} compiles; "
                   "expected >=1")
    elif warm["compiles"] != 0:
        failure = (f"warm child recompiled {warm['compiles']} kernels "
                   "for a warmed shape")
    elif warm["hits"] < 1:
        failure = ("warm child never ticked "
                   "vm_device_fleet_compile_cache_hits_total")
    else:
        print(f"compile-cache smoke: OK (cold {cold['compiles']} compiles "
              f"-> warm {warm['compiles']}, {warm['hits']} cache hits)")
        return 0
    print(f"compile-cache smoke: FAIL\n  {failure}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
