"""Declarative partition rules for the device mesh.

The serving engine used to wire hand-rolled ``shard_map`` closures per
kernel (manual in_specs/out_specs + explicit psum of partial moments) and
repeated ad-hoc ``NamedSharding(mesh, P(...))`` construction at every
device_put site.  This module replaces both with ONE rule table in the
``match_partition_rules`` style (SNIPPETS [2]/[3]): tile leaves are
*named*, a regex table maps each name onto the mesh axes, and every
placement/jit decision derives from that single source of truth.

Layout contract (the one place it is written down):

- packed sample planes and rollup blocks ``[S, ...]`` — ``ts``,
  ``values``, the delta planes' ``*_d2`` — shard their leading (series)
  row axis over ``AXIS_SERIES``; the sample/time axis stays local so
  windowed rollups never need halo exchange on this path.
- per-series vectors ``[S]`` — ``counts``, ``group_ids``, ``v0``,
  ``scale``, ``slots``, the delta planes' firsts/fdeltas — shard over
  ``AXIS_SERIES`` too.
- aggregated ``[G, T]`` outputs and scalars (``shift``, ``min_ts``) are
  replicated: every host pull reads one device's copy, and group moments
  cross shards through the XLA-inserted all-reduce (GSPMD), not a
  hand-written psum.

``shard_put`` pads the series axis to a multiple of the mesh's series
axis (kernels mask padded rows via ``counts == 0`` / ``TS_PAD``) and
counts uploaded bytes into the device-plane metrics.
"""

from __future__ import annotations

import re

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_SERIES = "series"
AXIS_STREAM = "stream"

# regex -> spec-per-rank: rank 1 leaves drop the trailing None axes.
# First match wins; unknown leaf names fail loudly (a silently replicated
# (S, N) plane would upload S*N bytes to EVERY device).
PARTITION_RULES: tuple[tuple[str, P], ...] = (
    # fleet-batched planes: a leading stream axis stacks every resident
    # window into one [B, ...] program (query/fleet.py) — the batch axis
    # shards over AXIS_STREAM, everything below it stays device-local so
    # per-stream rollups never exchange halos
    (r"^fleet_(ts|values|vals|out)$", P(AXIS_STREAM, None, None)),
    (r"^fleet_(counts|gids|v0)$", P(AXIS_STREAM, None)),
    (r"^fleet_(shift|min_ts|aggr)$", P(AXIS_STREAM)),
    # packed (S, N) sample planes / (S, T) rollup blocks / delta planes
    (r"^(ts|values|vals)$", P(AXIS_SERIES, None)),
    (r"_d2$", P(AXIS_SERIES, None)),
    # per-series vectors
    (r"^(counts|group_ids|gids|slots|v0|scale)$", P(AXIS_SERIES)),
    (r"(_first|_fdelta)$", P(AXIS_SERIES)),
    # aggregated outputs and traced scalars: replicated
    (r"^(out|shift|min_ts|phi)$", P()),
)


def match_partition_rules(name: str, ndim: int,
                          rules=PARTITION_RULES) -> P:
    """PartitionSpec for a named tile leaf (first matching rule wins),
    truncated to the leaf's rank.  Scalars are always replicated —
    partitioning a 0-d value is meaningless (SNIPPETS [3] does the same
    short-circuit)."""
    if ndim == 0:
        return P()
    for rule, spec in rules:
        if re.search(rule, name) is not None:
            return P(*spec[:ndim])
    raise ValueError(f"no partition rule matches tile leaf {name!r}")


def sharding_for(mesh: Mesh, name: str, ndim: int) -> NamedSharding:
    return NamedSharding(mesh, match_partition_rules(name, ndim))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def axis_multiple(mesh: Mesh, axis: str) -> int:
    """Padding multiple for tiles whose leading axis shards over `axis`
    (1 when the mesh doesn't carry that axis)."""
    return int(mesh.shape.get(axis, 1)) if mesh is not None else 1


def pad_rows_to_mesh(mesh: Mesh, a: np.ndarray, pad_value=0,
                     axis: str = AXIS_SERIES) -> np.ndarray:
    """Pad the leading axis to a multiple of the mesh axis it shards over
    so the shards are equal-sized."""
    n_sh = axis_multiple(mesh, axis)
    S = a.shape[0]
    S_pad = -(-S // n_sh) * n_sh
    if S_pad == S:
        return a
    widths = ((0, S_pad - S),) + ((0, 0),) * (a.ndim - 1)
    return np.pad(a, widths, constant_values=pad_value)


def shard_put(mesh: Mesh | None, name: str, a: np.ndarray, pad_value=0):
    """Place one named host array onto the mesh per the rule table
    (row-padded when row-sharded); single-device engines (mesh None)
    take the chunked upload path.  All device-plane uploads funnel
    through here or tile_cache.chunked_device_put, so
    vm_device_bytes_uploaded_total sees every H2D byte."""
    from ..models.tile_cache import chunked_device_put, timed_transfer
    if mesh is None:
        return chunked_device_put(np.asarray(a))
    import jax
    a = np.asarray(a)
    spec = match_partition_rules(name, a.ndim)
    if a.ndim and spec[0] in (AXIS_SERIES, AXIS_STREAM):
        a = pad_rows_to_mesh(mesh, a, pad_value, axis=spec[0])
    return timed_transfer(
        "device:upload", a.nbytes,
        lambda: jax.device_put(a, NamedSharding(mesh, spec)))


def input_shardings(mesh: Mesh, names_ndims) -> tuple:
    """in_shardings tuple for a jit'd kernel, one entry per (name, ndim)."""
    return tuple(sharding_for(mesh, n, d) for n, d in names_ndims)
