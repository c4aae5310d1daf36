"""AOT compiles of the served device path's kernels for a DESCRIBED v5e
(no chip attached): the TPU compiler installed here refuses what the real
chip's compiler would refuse — unaligned slices, too much fast memory, a
program that does not fit HBM, a kernel that cannot be partitioned — at
the dashboard shape the smoke serves (chip_smoke.py), in the dtype regime
the server runs under on a TPU (x64 off, f32 rebased tiles).

A compile is not a run: nothing here says anything about results or
times.  It guards every later PR at no chip time.

The topology is described INSIDE a module-scoped fixture (only one
process may load libtpu; xdist workers all import this file, so nothing
may touch it at import), every compile runs in this process, and the
persistent compile cache is off around them (an entry compiled for a
described chip cannot be read back without one).
"""

import os

import numpy as np
import pytest

# the dashboard deployment: 8192 series, 6h @ 15s + lookback in 25%
# headroom columns, 256 instances, 6h @ 60s grid
S, N, G, T = 8192, 2048, 256, 361
STEP, WINDOW = 60_000, 300_000
# fleet bucket: 4 streams of a 4096-series, 3h dashboard
FB, FS, FN, FG, FT = 4, 4096, 768, 64, 181


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip_regime(topo):
    """x64 off (as on a TPU server) and the persistent cache off, for the
    module's duration; both restored for whatever this worker runs next."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one(topo, chip_regime):
    """ShapeDtypeStruct factory placed on one described chip."""
    import jax
    from jax.sharding import SingleDeviceSharding
    sh = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sh)


def _cfg(func, t=T):
    from victoriametrics_tpu.ops.device_rollup import normalized_cfg
    from victoriametrics_tpu.ops.rollup_np import RollupConfig
    return normalized_cfg(func, RollupConfig(
        start=0, end=(t - 1) * STEP, step=STEP, window=WINDOW))


def _tile(sds, s=S, n=N):
    """(ts, values, counts) of an f32 rebased tile."""
    return (sds((s, n), np.int32), sds((s, n), np.float32),
            sds((s,), np.int32))


def _fits(compiled):
    """One v5e chip holds 16 GB; the compiler already refuses a program
    that does not fit, this keeps the margin visible."""
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes +
             m.temp_size_in_bytes)
    assert total < 8 << 30, f"{total >> 20} MiB for one dashboard window"


@pytest.mark.parametrize("func", ["rate", "increase", "stddev_over_time"])
def test_fused_rollup_aggregate(one, func):
    from victoriametrics_tpu.ops.device_rollup import rollup_aggregate_tile
    i32 = one((), np.int32)
    lowered = rollup_aggregate_tile.lower(
        func, "sum", *_tile(one), one((S,), np.int32), _cfg(func), G,
        i32, i32, one((S,), np.float32))
    # the group-sum matmul must ask for full f32 precision: the MXU's
    # default rounds f32 operands to bf16 (1.3e-3 on the chip, PR 22)
    assert "HIGHEST" in lowered.as_text()
    _fits(lowered.compile())


@pytest.mark.parametrize("func", ["rate", "max_over_time", "avg_over_time",
                                  "default_rollup"])
def test_rollup_tile(one, func):
    from victoriametrics_tpu.ops.device_rollup import rollup_tile
    _fits(rollup_tile.lower(func, *_tile(one), _cfg(func),
                            one((), np.int32),
                            one((S,), np.float32)).compile())


# the network panel's window (node-net8k): 4096 rows x 1856 columns of
# reset-corrected increments, 1024 hosts; the cold grid and the resident
# window's 4-step tail
NS, NN, NG = 4096, 1856, 1024


@pytest.mark.parametrize("func,t", [("rate", T), ("rate", 5),
                                    ("increase", T), ("irate", T)])
def test_fused_increments(one, func, t):
    from victoriametrics_tpu.ops.device_rollup import rollup_aggregate_tile
    i32 = one((), np.int32)
    lowered = rollup_aggregate_tile.lower(
        func, "sum", *_tile(one, NS, NN), one((NS,), np.int32),
        _cfg(func, t), NG, i32, i32, one((NS,), np.float32),
        increments=True)
    assert "HIGHEST" in lowered.as_text()
    _fits(lowered.compile())


def test_increments_tail_and_slide(one):
    """The wide window's append (16 f32 columns, donated) and its slide,
    which also sums what it drops (still donated)."""
    from victoriametrics_tpu.ops.device_rollup import (append_tile,
                                                       compact_tile)
    c = append_tile.lower(*_tile(one, NS, NN), one((NS, 16), np.int32),
                          one((NS, 16), np.float32),
                          one((NS,), np.int32)).compile()
    assert c.memory_analysis().alias_size_in_bytes >= NS * NN * 8
    i32 = one((), np.int32)
    c = compact_tile.lower(*_tile(one, NS, NN), i32, i32,
                           dropped_sums=True).compile()
    assert c.memory_analysis().alias_size_in_bytes >= NS * NN * 8
    _fits(c)


def test_topk_select_tile(one):
    from victoriametrics_tpu.ops.device_rollup import topk_select_tile
    _fits(topk_select_tile.lower("rate", *_tile(one), _cfg("rate"), 10,
                                 False, v0=one((S,), np.float32)).compile())


def test_append_tile_donated(one):
    """The rolling refresh: 4 new scrapes per series padded to 8 columns,
    host f64 values cast on device, old buffers donated."""
    from victoriametrics_tpu.ops.device_rollup import append_tile
    c = append_tile.lower(*_tile(one), one((S, 8), np.int32),
                          one((S, 8), np.float32),
                          one((S,), np.int32)).compile()
    assert c.memory_analysis().alias_size_in_bytes >= S * N * 8, \
        "append_tile no longer aliases the donated tile"


def test_rewind_append_tile_donated(one):
    """The rolling refresh under a dedup interval: the rows' samples past
    the rewind boundary dropped, the refetched (boundary, end] survivors
    padded to 8 columns appended, old buffers donated."""
    from victoriametrics_tpu.ops.device_rollup import rewind_append_tile
    c = rewind_append_tile.lower(*_tile(one), one((S, 8), np.int32),
                                 one((S, 8), np.float32),
                                 one((S,), np.int32),
                                 one((), np.int32)).compile()
    assert c.memory_analysis().alias_size_in_bytes >= S * N * 8, \
        "rewind_append_tile no longer aliases the donated tile"
    _fits(c)


def test_compact_tile_donated(one):
    from victoriametrics_tpu.ops.device_rollup import compact_tile
    i32 = one((), np.int32)
    _fits(compact_tile.lower(*_tile(one), i32, i32).compile())


def test_decode_tiles_rebased(one):
    """Cold upload: int16 second-order delta planes decoded on device into
    the rebased f32 tile."""
    from victoriametrics_tpu.ops.device_decode import decode_tiles
    v = lambda dt: one((S,), dt)  # noqa: E731
    d2 = one((S, N - 2), np.int16)
    _fits(decode_tiles.lower(v(np.int32), v(np.int32), d2, v(np.int32),
                             v(np.int32), d2, v(np.float32), v(np.int32),
                             N, np.float32, rebase=True).compile())


def _fleet_args(sds):
    return (sds((FB, FS, FN), np.int32), sds((FB, FS, FN), np.float32),
            sds((FB, FS), np.int32), sds((FB, FS), np.int32),
            sds((FB,), np.int32), sds((FB,), np.int32),
            sds((FB,), np.int32), sds((FB, FS), np.float32))


def test_fleet_step(one):
    from victoriametrics_tpu.ops.device_rollup import \
        fleet_rollup_aggregate_tile
    _fits(fleet_rollup_aggregate_tile.lower(
        "rate", _cfg("rate", FT), FG, *_fleet_args(one)).compile())


# -- four chips: one program across the 2x2 host -------------------------

def _on_mesh(mesh):
    """ShapeDtypeStruct factories placed per the partition-rule table."""
    import jax

    from victoriametrics_tpu.parallel.partition import sharding_for

    def make(name, shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=sharding_for(mesh, name, len(shape)))
    return make


# the four-chip cells' windows: the module's dashboard, and
# dash32k.refresh4's (32,768 rows x 1856 columns, 1024 groups)
MESH_SHAPES = {"dash8k": (S, N, G), "dash32k-4chip": (32768, 1856, 1024)}


def _mesh_tile(at, s, n):
    return (at("ts", (s, n), np.int32), at("values", (s, n), np.float32),
            at("counts", (s,), np.int32))


@pytest.mark.parametrize("shape", sorted(MESH_SHAPES))
def test_four_chip_series_sharded_step(topo, chip_regime, shape):
    """The serving engine's mesh step (auto_mesh: 4x1 series mesh): each
    chip rolls up its series shard, the [G, T] group moments cross chips
    in an XLA-inserted all-reduce."""
    from victoriametrics_tpu.parallel.mesh import (make_mesh,
                                                   sharded_rollup_aggregate)
    s, n, g = MESH_SHAPES[shape]
    mesh = make_mesh(topo.devices)
    at = _on_mesh(mesh)
    fn = sharded_rollup_aggregate(mesh, "rate", "sum", _cfg("rate"), g)
    c = fn.lower(
        *_mesh_tile(at, s, n), at("group_ids", (s,), np.int32),
        at("shift", (), np.int32), at("min_ts", (), np.int32),
        at("v0", (s,), np.float32)).compile()
    assert "all-reduce" in c.as_text() and "all-gather" not in c.as_text()
    # the tile is split, not replicated: each chip holds a quarter
    assert c.memory_analysis().argument_size_in_bytes < s * n * 8 // 2


def test_four_chip_decode_and_append(topo, chip_regime):
    """The rest of the mesh engine's refresh path stays row-local: the
    cold decode runs the single-device jit on row-sharded arrays (GSPMD
    partitions it by the arguments' shardings), the donated append its
    twin with the staged tail's shardings declared
    (parallel.mesh.cached_sharded_append_tile)."""
    from victoriametrics_tpu.ops.device_decode import decode_tiles
    from victoriametrics_tpu.parallel.mesh import (
        cached_sharded_append_tile, make_mesh)
    mesh = make_mesh(topo.devices)
    at = _on_mesh(mesh)
    v = lambda name, dt: at(name, (S,), dt)  # noqa: E731
    c = decode_tiles.lower(
        v("ts_first", np.int32), v("ts_fdelta", np.int32),
        at("ts_d2", (S, N - 2), np.int16), v("val_first", np.int32),
        v("val_fdelta", np.int32), at("val_d2", (S, N - 2), np.int16),
        v("scale", np.float32), v("counts", np.int32), N, np.float32,
        rebase=True).compile()
    assert "all-reduce" not in c.as_text() and \
        "all-gather" not in c.as_text()
    c = cached_sharded_append_tile(mesh).lower(
        *_mesh_tile(at, S, N), at("ts", (S, 8), np.int32),
        at("values", (S, 8), np.float32), v("counts", np.int32)).compile()
    assert c.memory_analysis().alias_size_in_bytes >= S * N * 8 // 4
    assert "all-gather" not in c.as_text()


@pytest.mark.parametrize("shape", sorted(MESH_SHAPES))
def test_four_chip_compact_tile_donated(topo, chip_regime, shape):
    """The slide of a row-sharded resident window (compact_window runs
    the single-device jit on the sharded tile): each chip compacts its
    own quarter in place - the donated planes are aliased, no sample
    plane is gathered, and the tile comes back sharded as it went in."""
    from victoriametrics_tpu.ops.device_rollup import compact_tile
    from victoriametrics_tpu.parallel.mesh import make_mesh
    s, n, _ = MESH_SHAPES[shape]
    at = _on_mesh(make_mesh(topo.devices))
    tile = _mesh_tile(at, s, n)
    i32 = at("shift", (), np.int32)
    c = compact_tile.lower(*tile, i32, i32).compile()
    assert c.memory_analysis().alias_size_in_bytes >= s * n * 8 // 4, \
        "compact_tile no longer aliases the donated, sharded tile"
    assert "all-gather" not in c.as_text() and \
        "all-reduce" not in c.as_text()
    assert [o.spec for o in c.output_shardings] == \
        [t.sharding.spec for t in tile]


def test_four_chip_fleet_step(topo, chip_regime):
    """The fleet plane's step on the stream mesh: each chip runs whole
    streams, no cross-chip reduction."""
    from victoriametrics_tpu.parallel.mesh import (
        cached_fleet_rollup_aggregate, make_fleet_mesh)
    mesh = make_fleet_mesh(topo.devices)
    at = _on_mesh(mesh)
    names = ("fleet_ts", "fleet_values", "fleet_counts", "fleet_gids",
             "fleet_aggr", "fleet_shift", "fleet_min_ts", "fleet_v0")
    one_dev = _fleet_args(lambda shape, dtype: (shape, dtype))
    fn = cached_fleet_rollup_aggregate(mesh, "rate", _cfg("rate", FT), FG)
    c = fn.lower(*(at(n, shape, dt)
                   for n, (shape, dt) in zip(names, one_dev))).compile()
    assert c.memory_analysis().argument_size_in_bytes < \
        FB * FS * FN * 8 // 2
