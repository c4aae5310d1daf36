"""Device-side block decode: ship compact delta planes, reconstruct on TPU.

The raw-tile path (ops/device_rollup.pack_series) moves 12 bytes/sample
(int32 ts + float64 val) over the host->device link. This module moves
~2-5 bytes/sample instead: second-order deltas quantized to the narrowest integer plane that fits (int8/int16/int32), and
reconstructs on device with two cumulative sums — the
`nearest-delta2 decode as associative scan` design from SURVEY §7 — fused
with the rollup kernel so decoded tiles never round-trip.

Host-side packing starts from decoded int64 mantissa arrays (the storage
layer's native varint decode runs at ~300M samples/s, so re-deltaing is
cheap); the win is the transfer, not host CPU.

Overflow safety: the tile is only eligible when every intermediate
(mantissa, delta) fits int32; otherwise callers fall back to the dense path.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from .rollup_np import RollupConfig

TS_PAD = np.int32(2**31 - 1)


@dataclasses.dataclass
class DeltaPlanes:
    """Host-built compact tile; all arrays np arrays ready for device_put."""
    ts_first: np.ndarray    # int32 [S], relative to start_ms
    ts_fdelta: np.ndarray   # int32 [S]
    ts_d2: np.ndarray       # int8/int16/int32 [S, max(N-2,1)]
    val_first: np.ndarray   # int32 [S] mantissas
    val_fdelta: np.ndarray  # int32 [S]
    val_d2: np.ndarray      # int8/int16/int32 [S, max(N-2,1)]
    scale: np.ndarray       # float32/float64 [S] = 10^exponent
    counts: np.ndarray      # int32 [S]

    @property
    def nbytes(self) -> int:
        return sum(getattr(self, f.name).nbytes
                   for f in dataclasses.fields(self))


def _narrowest_plane(d2: np.ndarray):
    if d2.size == 0:
        return np.int8
    m = np.abs(d2).max()
    if m < 127:
        return np.int8
    if m < 32767:
        return np.int16
    return np.int32


def pack_delta_planes(series, start_ms: int, value_dtype=np.float32,
                      rebase: bool = False) -> DeltaPlanes | None:
    """series: [(ts_ms int64[], mantissas int64[], exponent)] — returns None
    when any series needs >int32 intermediates (caller falls back).

    `rebase=True` additionally requires every m - m[0] to fit int32: the
    f32 tile decode reconstructs REBASED mantissas (cumsum from zero), so
    the running offsets are the intermediates (see tpu_engine f32 design)."""
    S = len(series)
    if S == 0:
        return None
    counts = np.array([len(t) for t, _, _ in series], dtype=np.int32)
    if (counts < 1).any():
        return None
    N = int(counts.max())
    ts_first = np.zeros(S, dtype=np.int64)
    ts_fd = np.zeros(S, dtype=np.int64)
    val_first = np.zeros(S, dtype=np.int64)
    val_fd = np.zeros(S, dtype=np.int64)
    scale = np.ones(S, dtype=value_dtype)
    ts_d2 = np.zeros((S, max(N - 2, 1)), dtype=np.int64)
    val_d2 = np.zeros((S, max(N - 2, 1)), dtype=np.int64)
    for i, (ts, m, exp) in enumerate(series):
        rel = np.asarray(ts, dtype=np.int64) - start_ms
        m = np.asarray(m, dtype=np.int64)
        if rel.size and (np.abs(rel).max() >= 2**31 or
                         np.abs(m).max() >= 2**31):
            return None
        if rebase and m.size and np.abs(m - m[0]).max() >= 2**31:
            return None
        ts_first[i] = rel[0]
        val_first[i] = m[0]
        scale[i] = np.float64(10.0) ** exp
        if rel.size >= 2:
            td = np.diff(rel)
            vd = np.diff(m)
            if np.abs(td).max() >= 2**31 or np.abs(vd).max() >= 2**31:
                return None
            ts_fd[i] = td[0]
            val_fd[i] = vd[0]
            if rel.size >= 3:
                t2 = np.diff(td)
                v2 = np.diff(vd)
                if np.abs(t2).max() >= 2**31 or np.abs(v2).max() >= 2**31:
                    return None
                ts_d2[i, :t2.size] = t2
                val_d2[i, :v2.size] = v2
    return DeltaPlanes(
        ts_first=ts_first.astype(np.int32),
        ts_fdelta=ts_fd.astype(np.int32),
        ts_d2=ts_d2.astype(_narrowest_plane(ts_d2)),
        val_first=val_first.astype(np.int32),
        val_fdelta=val_fd.astype(np.int32),
        val_d2=val_d2.astype(_narrowest_plane(val_d2)),
        scale=scale,
        counts=counts,
    )


def _reconstruct(first, fdelta, d2, counts, n):
    """Device: values[i] = first + sum_{k<i} d1[k], d1 = [fdelta, fdelta+cum
    d2...] — double prefix sum in int32."""
    import jax.numpy as jnp
    S = first.shape[0]
    # d1 row: [fdelta, d2...] cumsum -> deltas between consecutive samples
    d1 = jnp.concatenate(
        [fdelta[:, None], d2.astype(jnp.int32)], axis=1)[:, :max(n - 1, 1)]
    d1 = jnp.cumsum(d1, axis=1)
    vals = jnp.concatenate([first[:, None],
                            first[:, None] + jnp.cumsum(d1, axis=1)], axis=1)
    return vals[:, :n]


@functools.partial(__import__("jax").jit,
                   static_argnames=("n", "value_dtype", "rebase"))
def decode_tiles(planes_ts_first, planes_ts_fd, planes_ts_d2,
                 planes_val_first, planes_val_fd, planes_val_d2,
                 scale, counts, n: int, value_dtype=np.float32,
                 rebase: bool = False):
    """On-device decode of delta planes -> (ts int32 [S,n], vals [S,n]).

    `rebase=True` reconstructs mantissas from ZERO instead of the first
    mantissa — the tile then holds v - v0 exactly in integer space before
    the one dtype-rounding scale multiply (the f32 tile contract)."""
    import jax.numpy as jnp
    ts = _reconstruct(planes_ts_first, planes_ts_fd, planes_ts_d2, counts, n)
    valid = jnp.arange(n, dtype=jnp.int32)[None, :] < counts[:, None]
    ts = jnp.where(valid, ts, TS_PAD)
    vfirst = (planes_val_first * 0) if rebase else planes_val_first
    mant = _reconstruct(vfirst, planes_val_fd, planes_val_d2, counts, n)
    vals = mant.astype(value_dtype) * scale[:, None].astype(value_dtype)
    return ts, vals


@functools.partial(__import__("jax").jit,
                   static_argnames=("func", "cfg", "n", "value_dtype"))
def decode_and_rollup(func: str, planes_ts_first, planes_ts_fd, planes_ts_d2,
                      planes_val_first, planes_val_fd, planes_val_d2,
                      scale, counts, cfg: RollupConfig, n: int,
                      value_dtype=np.float32):
    """Fused on-device decode + rollup -> [S, T]."""
    from .device_rollup import rollup_tile
    ts, vals = decode_tiles(planes_ts_first, planes_ts_fd, planes_ts_d2,
                            planes_val_first, planes_val_fd, planes_val_d2,
                            scale, counts, n, value_dtype)
    return rollup_tile(func, ts, vals, counts, cfg)
