"""Device-mesh sharding for the query engine.

The reference scales reads by fanning a query out to every vmstorage node and
merging per-node partial aggregates (lib/vmselectapi scatter-gather +
aggr_incremental.go map-reduce). On TPU the same shape becomes: shard the
series axis over a `jax.sharding.Mesh` and let GSPMD partition the
segment-reduction — the cross-shard merge is the XLA-inserted all-reduce,
not a hand-written psum loop.

AXIS_SERIES ("series") is data-parallel over series: the single-device
fused kernel (ops.device_rollup.rollup_aggregate_tile) is jit'd with
declarative in/out shardings from the partition-rule table
(parallel/partition.py); each device rolls up its series shard and XLA
reduces the [G, T] group moments across shards.  AXIS_STREAM shards the
fleet's stacked windows the same way (make_fleet_mesh).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..ops.rollup_np import RollupConfig
from .partition import (AXIS_SERIES, AXIS_STREAM, input_shardings,
                        replicated, sharding_for)


def make_mesh(devices=None) -> Mesh:
    """One-axis mesh sharding the series axis over the given devices
    (every visible one by default)."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (AXIS_SERIES,))


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
    query; without memoization every call would build a fresh jit and miss
    its cache."""
    return sharded_rollup_aggregate(mesh, rollup_func, aggr, cfg, num_groups)


def sharded_rollup_aggregate(mesh: Mesh, rollup_func: str, aggr: str,
                             cfg: RollupConfig, num_groups: int):
    """Build the jitted aggr(rollup(...)) that runs series-sharded on the
    mesh: the returned jit itself (its `_cache_size` is what
    tpu_engine.timed_kernel_call reads to book a compile), named
    `sharded_rollup_aggregate` so the device trace tells the mesh step
    (`jit_sharded_rollup_aggregate`) from the fleet's `jit_step`.

    Declarative GSPMD partitioning: the body of the single-device fused
    kernel (ops.device_rollup.rollup_aggregate_tile: rollup_tile, then
    aggregate_groups) with in/out shardings derived from the
    partition-rule table — the per-shard segment moments and the
    cross-shard reduction are one XLA program, with the all-reduce
    inserted by the partitioner instead of a hand-rolled shard_map
    closure + psum.

    Inputs: ts [S, N] int32, values [S, N], counts [S] int32,
    group_ids [S] int32, shift int32 scalar (rolling-tile grid rebase, 0
    for freshly built tiles), min_ts int32 scalar, v0 [S] (per-series
    rebase offsets of f32 tiles) or None; S must be divisible by the
    series-axis size. Output: [G, T] fully replicated.
    """
    from ..ops.device_rollup import aggregate_groups, rollup_tile
    in_sh = input_shardings(mesh, (("ts", 2), ("values", 2), ("counts", 1),
                                   ("group_ids", 1), ("shift", 0),
                                   ("min_ts", 0), ("v0", 1)))

    @functools.partial(jax.jit, in_shardings=in_sh,
                       out_shardings=replicated(mesh))
    def sharded_rollup_aggregate(ts, values, counts, group_ids, shift,
                                 min_ts, v0):
        with jax.named_scope("rollup"):
            rolled = rollup_tile(rollup_func, ts - jnp.int32(shift), values,
                                 counts, cfg, min_ts, v0)
        with jax.named_scope("group_moments"):
            return aggregate_groups(aggr, rolled, group_ids, num_groups)

    return sharded_rollup_aggregate


@functools.lru_cache(maxsize=8)
def cached_sharded_append_tile(mesh: Mesh):
    """ops.device_rollup.append_tile for a row-sharded resident tile: the
    same body and donation, with the staged tail's shardings DECLARED from
    the rule table, so a tick's host arrays ride this one call onto the
    devices that hold their rows (a plain jit would leave the placement of
    uncommitted arguments to the partitioner)."""
    from ..ops.device_rollup import _append_tile_body
    tile = input_shardings(mesh, (("ts", 2), ("values", 2), ("counts", 1)))

    @functools.partial(jax.jit, in_shardings=tile + tile,
                       out_shardings=tile, donate_argnums=(0, 1, 2))
    def sharded_append_tile(ts, values, counts, new_ts, new_values,
                            new_counts):
        return _append_tile_body(ts, values, counts, new_ts, new_values,
                                 new_counts)

    return sharded_append_tile
