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


@dataclasses.dataclass
class StagedRows:
    """The cold tile build's host staging of S fetched rows: the planes, or
    None where a row needs more than int32 (the dense tile then carries the
    rows, its values from the flat column here), and for a rebased (f32)
    tile the host float64 offsets `v0` and the wide-range flag `risky`.
    `path` is the code that staged them: "native" or its NumPy twin
    "python"."""
    planes: DeltaPlanes | None
    v0: np.ndarray | None
    risky: bool
    path: str
    vals: np.ndarray        # float64, every row's values back to back
    counts: np.ndarray      # int64 [S]


def _plane_dtype(max_abs: int):
    """The narrowest integer plane holding every |d2| <= max_abs."""
    if max_abs < 127:
        return np.int8
    if max_abs < 32767:
        return np.int16
    return np.int32


def _fits_i32(x: np.ndarray) -> np.ndarray:
    # a range test, NOT np.abs: abs(INT64_MIN) -- the V_NAN sentinel --
    # overflows back to INT64_MIN and would pass an abs-< test
    return (x > -(2 ** 31)) & (x < 2 ** 31)


def _row_starts(counts: np.ndarray) -> np.ndarray:
    starts = np.zeros(counts.size, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    return starts


def _pow10_rows(exps: np.ndarray) -> np.ndarray:
    """10.0 ** e for every row's exponent, by the float power the per-row
    statement took (numpy's scalar power reads the same), once a distinct
    exponent."""
    uniq, inv = np.unique(exps, return_inverse=True)
    return np.array([10.0 ** int(e) for e in uniq], np.float64)[inv]


def _pack_py(ts, m, starts, counts, start_ms: int, rebase: bool, width: int):
    """The NumPy twin of native.pack_delta_planes (the program's fallback
    where the library is missing): the same tuple from differences over the
    whole concatenation and one scatter, or None where it refuses."""
    S, n = counts.size, ts.size
    if (counts < 1).any():
        return None
    rel = ts - start_ms
    if not (_fits_i32(rel).all() and _fits_i32(m).all()):
        return None
    row = np.repeat(np.arange(S), counts)
    pos = np.arange(n) - np.repeat(starts, counts)
    if rebase and not _fits_i32(m - m[starts][row]).all():
        return None
    # element k of a first difference spans samples k, k+1 and of a second
    # k..k+2; it belongs to a row where the last of them is no row's start
    td, vd = np.diff(rel), np.diff(m)
    in1 = pos[1:] >= 1
    if not (_fits_i32(td[in1]).all() and _fits_i32(vd[in1]).all()):
        return None
    in2 = pos[2:] >= 2
    t2, v2 = np.diff(td)[in2], np.diff(vd)[in2]
    if not (_fits_i32(t2).all() and _fits_i32(v2).all()):
        return None
    two = np.flatnonzero(counts >= 2)
    fd = [np.zeros(S, np.int32) for _ in range(2)]
    fd[0][two], fd[1][two] = td[starts[two]], vd[starts[two]]
    dest = row[2:][in2] * width + pos[2:][in2] - 2
    d2 = [np.zeros(S * width, np.int32) for _ in range(2)]
    d2[0][dest], d2[1][dest] = t2, v2
    return (rel[starts].astype(np.int32), fd[0], d2[0].reshape(S, width),
            m[starts].astype(np.int32), fd[1], d2[1].reshape(S, width),
            np.abs(t2).max(initial=0), np.abs(v2).max(initial=0))


def _gates_py(vals, m, starts, counts, gate: float):
    """The NumPy twin of the native pass's rebase gates: (v0, risky)."""
    S = counts.size
    row = np.repeat(np.arange(S), counts)
    full = counts > 0
    v0 = np.zeros(S, np.float64)
    first = vals[starts[full]]
    v0[full] = np.where(np.isfinite(first), first, 0.0)
    # the staleness marker is a signalling NaN: arithmetic on it is invalid
    with np.errstate(over="ignore", invalid="ignore"):
        if (np.isfinite(vals) & (np.abs(vals - v0[row]) >= gate)).any():
            return v0, True
    at = np.flatnonzero(_fits_i32(m))
    ra = row[at]
    base = np.zeros(S, np.int64)
    lead = np.flatnonzero(np.diff(ra, prepend=-1))  # each row's first sane
    base[ra[lead]] = m[at[lead]]
    return v0, bool((np.abs(m[at] - base[ra]) >= gate).any())


def _pack(ts, m, exps, counts, start_ms: int, value_dtype, rebase: bool,
          vals=None, gate: float = 0.0):
    """Flat rows (int64 ts and mantissas back to back, `counts` a row) ->
    (DeltaPlanes or None, v0, risky, path); v0 and risky are the rebase
    gates where `vals` is given."""
    from .. import native
    S = int(counts.size)
    starts = _row_starts(counts)
    width = max(int(counts.max(initial=0)) - 2, 1)
    got = native.pack_delta_planes(ts, m, starts, start_ms, rebase, width,
                                   vals, gate)
    if got is not None:
        raw, v0, risky = got
        path = "native"
    else:
        raw = _pack_py(ts, m, starts, counts, start_ms, rebase, width)
        v0, risky = (_gates_py(vals, m, starts, counts, gate)
                     if vals is not None else (None, False))
        path = "python"
    if raw is None or S == 0:
        return None, v0, risky, path
    tf, tfd, td2, vf, vfd, vd2, tmax, vmax = raw
    planes = DeltaPlanes(
        ts_first=tf, ts_fdelta=tfd,
        ts_d2=td2.astype(_plane_dtype(int(np.max(tmax))), copy=False),
        val_first=vf, val_fdelta=vfd,
        val_d2=vd2.astype(_plane_dtype(int(np.max(vmax))), copy=False),
        scale=_pow10_rows(exps).astype(value_dtype),
        counts=counts.astype(np.int32))
    return planes, v0, risky, path


def pack_delta_planes(series, start_ms: int, value_dtype=np.float32,
                      rebase: bool = False) -> DeltaPlanes | None:
    """series: [(ts_ms int64[], mantissas int64[], exponent)] — returns None
    when any series needs >int32 intermediates (caller falls back).

    `rebase=True` additionally requires every m - m[0] to fit int32: the
    f32 tile decode reconstructs REBASED mantissas (cumsum from zero), so
    the running offsets are the intermediates (see tpu_engine f32 design).
    A wrapper over the batched pass that the cold build runs."""
    if not series:
        return None
    ts, m, exps = zip(*series)
    counts = np.fromiter(map(len, ts), np.int64, len(ts))
    return _pack(np.concatenate(ts).astype(np.int64, copy=False),
                 np.concatenate(m).astype(np.int64, copy=False),
                 np.asarray(exps, np.int64), counts, start_ms, value_dtype,
                 rebase)[0]


def stage_rows(ts_rows, val_rows, start_ms: int, value_dtype=np.float32,
               rebase: bool = False, gate: float = 0.0) -> StagedRows:
    """The cold tile build's host staging, batched over every row: one
    concatenation, one grouped float -> decimal conversion, one pack of
    the delta planes (native, else its NumPy twin). `rebase` stages an f32
    tile: v0 is then each row's DECODED first value (mantissa x 10^e,
    what the device's rebased decode subtracts) where the planes hold, the
    first finite value where the dense tile must carry the rows, and
    `risky` the wide-range flag at `gate` (tpu_engine.F32_SAFE_RANGE)."""
    from . import decimal as dec
    S = len(ts_rows)
    counts = np.fromiter(map(len, ts_rows), np.int64, S)
    ts = (np.concatenate(ts_rows) if S else np.zeros(0)).astype(
        np.int64, copy=False)
    vals = (np.concatenate(val_rows) if S else np.zeros(0)).astype(
        np.float64, copy=False)
    starts = _row_starts(counts)
    full = counts > 0
    m, exps_full = dec.float_to_decimal_grouped(vals, starts[full])
    exps = np.zeros(S, np.int64)
    exps[full] = exps_full
    # float_to_decimal converts 8 samples or fewer by repr() and can round
    # a 17-digit float an ulp apart from the grouped pass: such rows keep
    # exactly what the per-row conversion gives them
    for i in np.flatnonzero(full & (counts <= 8)):
        a, b = starts[i], starts[i] + counts[i]
        m[a:b], exps[i] = dec.float_to_decimal(vals[a:b])
    planes, v0, risky, path = _pack(ts, m, exps, counts, start_ms,
                                    value_dtype, rebase,
                                    vals if rebase else None, gate)
    if planes is not None and rebase:
        v0 = m[starts].astype(np.float64) * _pow10_rows(exps)
        v0[~np.isfinite(v0)] = 0.0
    return StagedRows(planes, v0, risky, path, vals, counts)


def counter_increments(vals: np.ndarray, counts: np.ndarray,
                       prev: np.ndarray | None = None):
    """Each row's reset-corrected increments, in float64, for the value
    plane of a wide float32 tile (tpu_engine.V0Info): `vals` holds the
    rows back to back, `counts` samples a row. inc[i] = v[i] - v[i-1]
    where the counter did not fall; where it fell, upstream's
    removeCounterResets rule (rollup_np.remove_counter_resets): a drop
    under 1/8 of the value before it adds 0, a larger one v[i]. A row's
    first sample adds 0, unless `prev` gives the row's value before it
    (NaN: none). Returns (inc, last): `last` is each row's last value,
    `prev` where a row is empty."""
    counts = np.asarray(counts, np.int64)
    starts = _row_starts(counts)
    full = counts > 0
    before = np.empty_like(vals)
    before[1:] = vals[:-1]
    before[starts[full]] = np.nan if prev is None else prev[full]
    with np.errstate(invalid="ignore"):
        inc = vals - before
        fell = inc < 0
        b, v = before[fell], vals[fell]
        inc[fell] = np.where((b - v) * 8 < b, 0.0, v)
    inc[np.isnan(before)] = 0.0
    last = np.full(counts.size, np.nan) if prev is None else prev.copy()
    last[full] = vals[starts[full] + counts[full] - 1]
    return inc, last


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
