"""Device-mesh sharding for the query engine.

The reference scales reads by fanning a query out to every vmstorage node and
merging per-node partial aggregates (lib/vmselectapi scatter-gather +
aggr_incremental.go map-reduce). On TPU the same shape becomes: shard the
series axis over a `jax.sharding.Mesh` and let GSPMD partition the
segment-reduction — the cross-shard merge is the XLA-inserted all-reduce,
not a hand-written psum loop.

Two parallel axes are first-class:

- AXIS_SERIES ("series"): data-parallel over series. The single-device
  fused kernel (ops.device_rollup.rollup_aggregate_tile) is jit'd with
  declarative in/out shardings from the partition-rule table
  (parallel/partition.py); each device rolls up its series shard and XLA
  reduces the [G, T] group moments across shards.
- AXIS_TIME ("time"): sequence-parallel over the *sample* axis (the
  long-context analog). Each device holds a contiguous time-slice of every
  series' samples; rollup windows crossing the slice boundary need the tail
  of the left neighbor, exchanged with `lax.ppermute` (ring halo exchange,
  like ring attention passes KV blocks). This path keeps an explicit
  shard_map: the halo exchange is a genuinely manual collective that has
  no declarative spelling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.device_rollup import rollup_tile
from ..ops.rollup_np import RollupConfig
from .partition import (AXIS_SERIES, AXIS_STREAM, AXIS_TIME,
                        input_shardings, replicated, sharding_for)


def make_mesh(n_series: int | None = None, n_time: int = 1,
              devices=None) -> Mesh:
    """Build a (series, time) mesh over the available devices."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if n_series is None:
        n_series = n // n_time
    if n_series * n_time != n:
        raise ValueError(f"mesh {n_series}x{n_time} != {n} devices")
    arr = np.asarray(devices).reshape(n_series, n_time)
    return Mesh(arr, (AXIS_SERIES, AXIS_TIME))


def make_fleet_mesh(devices=None) -> Mesh:
    """One-axis mesh sharding the fleet's leading STREAM axis over every
    device: each device runs a contiguous slice of the resident streams'
    whole programs (rollup windows never cross streams, so this axis
    needs no halo exchange or cross-device reduction at all)."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (AXIS_STREAM,))


@functools.lru_cache(maxsize=256)
def cached_fleet_rollup_aggregate(mesh: Mesh, rollup_func: str,
                                  cfg: RollupConfig, num_groups: int):
    """Memoized fleet kernel for one bucket shape: the [B, S, N] planes
    shard over AXIS_STREAM per the partition-rule table; the aggregate is
    a per-stream traced code, so one compile covers every aggregate mix
    (see ops.device_rollup.fleet_rollup_aggregate_impl).  The [B, G, T]
    output stays stream-sharded — the single host pull gathers it."""
    from ..ops.device_rollup import fleet_rollup_aggregate_impl
    in_sh = input_shardings(
        mesh, (("fleet_ts", 3), ("fleet_values", 3), ("fleet_counts", 2),
               ("fleet_gids", 2), ("fleet_aggr", 1), ("fleet_shift", 1),
               ("fleet_min_ts", 1), ("fleet_v0", 2)))

    @functools.partial(jax.jit, in_shardings=in_sh,
                       out_shardings=sharding_for(mesh, "fleet_out", 3))
    def step(fleet_ts, fleet_values, fleet_counts, fleet_gids, fleet_aggr,
             fleet_shift, fleet_min_ts, fleet_v0):
        return fleet_rollup_aggregate_impl(
            rollup_func, cfg, num_groups, fleet_ts, fleet_values,
            fleet_counts, fleet_gids, fleet_aggr, fleet_shift,
            fleet_min_ts, fleet_v0)

    return step


@functools.lru_cache(maxsize=256)
def cached_sharded_rollup_aggregate(mesh: Mesh, rollup_func: str, aggr: str,
                                    cfg: RollupConfig, num_groups: int):
    """Memoized sharded_rollup_aggregate: the serving engine calls this per
    query; without memoization every call would build a fresh closure and
    miss jax's jit cache."""
    return sharded_rollup_aggregate(mesh, rollup_func, aggr, cfg, num_groups)


def sharded_rollup_aggregate(mesh: Mesh, rollup_func: str, aggr: str,
                             cfg: RollupConfig, num_groups: int):
    """Build a jitted aggr(rollup(...)) running series-sharded on the mesh.

    Declarative GSPMD partitioning: the SAME fused kernel the single-device
    engine runs (ops.device_rollup.rollup_aggregate_tile) is jit'd with
    in/out shardings derived from the partition-rule table — the
    per-shard segment moments and the cross-shard reduction are one XLA
    program, with the all-reduce inserted by the partitioner instead of a
    hand-rolled shard_map closure + psum.

    Inputs: ts [S, N] int32, values [S, N], counts [S] int32,
    group_ids [S] int32, shift int32 scalar (rolling-tile grid rebase, 0
    for freshly built tiles), min_ts int32 scalar, v0 [S] (per-series
    rebase offsets of f32 tiles; zeros otherwise); S must be divisible by
    the series-axis size. Output: [G, T] fully replicated.
    """
    from ..ops.device_rollup import rollup_aggregate_tile
    in_sh = input_shardings(mesh, (("ts", 2), ("values", 2), ("counts", 1),
                                   ("group_ids", 1), ("shift", 0),
                                   ("min_ts", 0), ("v0", 1)))

    @functools.partial(jax.jit, in_shardings=in_sh,
                       out_shardings=replicated(mesh))
    def step(ts, values, counts, group_ids, shift, min_ts, v0):
        return rollup_aggregate_tile(rollup_func, aggr, ts, values, counts,
                                     group_ids, cfg, num_groups, shift,
                                     min_ts, v0)

    def call(ts, values, counts, group_ids, shift, min_ts, v0=None):
        if v0 is None:
            v0 = jnp.zeros(ts.shape[0], values.dtype)
        return step(ts, values, counts, group_ids, jnp.int32(shift),
                    jnp.int32(min_ts), v0)

    return call


def time_sharded_rollup(mesh: Mesh, rollup_func: str, cfg: RollupConfig,
                        halo: int):
    """Sequence-parallel rollup: the sample axis is sharded over AXIS_TIME.

    Each device holds a contiguous chunk of every series' samples (padded to
    equal chunk length; chunk boundaries aligned to time so chunk i's samples
    all precede chunk i+1's). Before rolling up, each device receives the
    trailing `halo` samples of its left neighbor via lax.ppermute — enough to
    cover one lookback window plus the real-prev-value gather — then computes
    only the output steps whose windows it owns.

    Output-step ownership: step j belongs to the device whose time range
    contains the step's timestamp; here we simply split the T output steps
    contiguously across AXIS_TIME and all-gather at the end.

    Counter-reset correction stays exact across chunks because the halo
    overlap lets each device reconstruct resets local to its windows; resets
    older than one window+halo do not affect windowed rollups (they cancel in
    the window difference).
    """
    if rollup_func in _TIME_SHARD_UNSUPPORTED:
        raise ValueError(
            f"{rollup_func} needs whole-series context (first sample) and "
            "cannot run on the time-sharded path; use series sharding")
    n_time = mesh.shape[AXIS_TIME]
    T_total = (cfg.end - cfg.start) // cfg.step + 1
    if T_total % n_time:
        raise ValueError(f"T={T_total} not divisible by time axis {n_time}")
    t_shard = T_total // n_time

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(AXIS_SERIES, AXIS_TIME), P(AXIS_SERIES, AXIS_TIME),
                  P(AXIS_SERIES, AXIS_TIME)),
        out_specs=P(AXIS_SERIES, AXIS_TIME))
    def step(ts, values, valid):
        # ring halo: receive left neighbor's tail
        idx = jax.lax.axis_index(AXIS_TIME)
        perm = [(i, (i + 1) % n_time) for i in range(n_time)]
        tail_ts = jax.lax.ppermute(ts[:, -halo:], AXIS_TIME, perm)
        tail_v = jax.lax.ppermute(values[:, -halo:], AXIS_TIME, perm)
        tail_ok = jax.lax.ppermute(valid[:, -halo:], AXIS_TIME, perm)
        # device 0 has no left neighbor: its received halo is garbage; mask.
        tail_ok = jnp.where(idx == 0, False, tail_ok)
        ts_ext = jnp.concatenate([tail_ts, ts], axis=1)
        v_ext = jnp.concatenate([tail_v, values], axis=1)
        ok_ext = jnp.concatenate([tail_ok, valid], axis=1)
        counts = jnp.sum(ok_ext, axis=1).astype(jnp.int32)
        # Compact valid samples to the front (stable sort on the invalid
        # flag keeps time order: halo precedes local by construction).
        order = jnp.argsort(jnp.where(ok_ext, 0, 1), axis=1, stable=True)
        ts_c = jnp.take_along_axis(jnp.where(ok_ext, ts_ext, 2**31 - 1), order, axis=1)
        v_c = jnp.take_along_axis(jnp.where(ok_ext, v_ext, 0.0), order, axis=1)
        # local output grid slice
        local_cfg = RollupConfig(
            start=cfg.start, end=cfg.start + (t_shard - 1) * cfg.step,
            step=cfg.step, window=cfg.window)
        shift = idx * t_shard * cfg.step
        rolled = rollup_tile_shifted(rollup_func, ts_c, v_c, counts,
                                     local_cfg, shift)
        return rolled

    return jax.jit(step)


# Funcs needing whole-series context that chunked time sharding cannot see.
_TIME_SHARD_UNSUPPORTED = frozenset({"lifetime"})

# Funcs returning absolute times: rollup_tile adds cfg.start back, so the
# chunk's grid shift must be re-added on top.
_TIME_VALUED = frozenset({"tfirst_over_time", "tlast_over_time", "timestamp"})


def rollup_tile_shifted(func, ts, values, counts, cfg, shift):
    """rollup_tile with the output grid shifted by a traced offset (used by
    time-sharded evaluation where each device owns a grid slice)."""
    out = rollup_tile(func, ts - shift, values, counts, cfg)
    if func in _TIME_VALUED:
        out = out + shift.astype(out.dtype) / 1e3
    return out
