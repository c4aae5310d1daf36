"""f32 device tiles: rebased-value design differential bounds.

Real TPUs have no native float64, so device tiles there are float32 holding
REBASED values v - v0 with exact integer-mantissa rebasing on device (see
query/tpu_engine.py f32 design comment). These tests force an f32 engine on
the CPU backend and bound the device-vs-host-f64 error on adversarial data:
counters with a LARGE base (1e9+) and small increments — the case plain-f32
tiles would destroy (1e9 has ~64-unit ulp in f32; a 5m rate window moves by
~100s of units).

Reference precedent for lossy device numerics: the storage codec itself
quantizes values (lib/encoding/nearest_delta.go:15 precisionBits).
"""

import numpy as np
import pytest

from victoriametrics_tpu.ops import rollup_np
from victoriametrics_tpu.ops.rollup_np import RollupConfig
from victoriametrics_tpu.query import tpu_engine
from victoriametrics_tpu.query.tpu_engine import (
    TPUEngine, try_aggr_rollup_tpu, try_quantile_rollup_tpu, try_rollup_tpu,
    try_topk_rollup_tpu)
from victoriametrics_tpu.storage.metric_name import MetricName
from victoriametrics_tpu.storage.storage import SeriesData

START = 1_753_700_000_000
CFG = RollupConfig(start=START + 600_000, end=START + 1_800_000,
                   step=60_000, window=300_000)
BASE = 1.0e9  # large counter base: the f32 killer


def _series(n_series=96, n=140, base=BASE, resets=False, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_series):
        ts = np.arange(n, dtype=np.int64) * 15_000 + START
        ts = ts + rng.integers(-2000, 2000, n)
        ts.sort()
        v = base * (1 + i / 7) + np.cumsum(rng.integers(0, 50, n)) \
            .astype(np.float64)
        if resets and i % 3 == 0:
            p = int(rng.integers(n // 3, n))
            v[p:] -= v[p] - base / 1000  # reset near zero, then re-grow
        mn = MetricName.from_dict({"__name__": "m", "i": str(i)})
        out.append(SeriesData(mn, ts, v, raw_name=mn.marshal()))
    return out


def _host_rows(func, series):
    pairs = [(sd.timestamps, sd.values) for sd in series]
    return rollup_np.rollup_batch(func, pairs, CFG)


@pytest.fixture(scope="module")
def engine():
    return TPUEngine(value_dtype=np.float32, min_series=2)


def _assert_close(dev, host, rtol, label):
    dev = np.asarray(dev, dtype=np.float64)
    host = np.asarray(host, dtype=np.float64)
    assert dev.shape == host.shape, label
    np.testing.assert_array_equal(np.isnan(dev), np.isnan(host),
                                  err_msg=label)
    m = ~np.isnan(host)
    scale = np.maximum(np.abs(host[m]), 1e-3)
    err = np.abs(dev[m] - host[m]) / scale
    assert err.size == 0 or float(err.max()) < rtol, \
        f"{label}: max rel err {err.max():.3g} >= {rtol}"


# -- shift-invariant funcs run directly on rebased f32 tiles ---------------

@pytest.mark.parametrize("func,rtol", [
    ("rate", 1e-5), ("increase", 1e-5), ("delta", 1e-5), ("irate", 1e-5),
    ("idelta", 1e-5), ("changes", 1e-5), ("count_over_time", 1e-5),
    # variance centering subtracts the whole-series mean; window-local
    # spread is ~100x smaller than the rebased magnitude, so the E[x^2]
    # cancellation costs ~1 decimal digit extra
    ("stddev_over_time", 1e-4),
    # least-squares slope: the moment-sum cancellation amplifies f32
    # rounding ~10x beyond the plain window arithmetic
    ("deriv", 1e-3),
])
def test_direct_funcs_large_base(engine, func, rtol):
    series = _series()
    rows = try_rollup_tpu(engine, func, series, CFG, ())
    assert rows is not None, "device path must engage on f32 tiles"
    host = _host_rows(func, series)
    # bound: one f32 rounding of the REBASED magnitude, amplified by the
    # window arithmetic — 1e-5 relative leaves ~100x headroom over 2^-23
    _assert_close(np.stack(rows), host, rtol, func)


def test_counter_resets_small_base(engine):
    """Resets at small magnitude (< 2^24): the f32 reset correction stays
    exact enough — classification (8x-drop rule, rollup.go:921) and values
    must track the host."""
    series = _series(base=1.0e5, resets=True, seed=9)
    for func in ("rate", "increase"):
        rows = try_rollup_tpu(engine, func, series, CFG, ())
        assert rows is not None
        _assert_close(np.stack(rows), _host_rows(func, series), 1e-4,
                      f"{func}+resets")


def test_counter_resets_large_base_falls_back(engine):
    """A reset from a 1e9 base pushes the REBASED magnitude past 2^24: the
    tile's value plane holds reset-corrected increments, on which the
    counter family runs (rate and increase equal the host's f64 rows, the
    fused sum too); every other value-dependent func refuses the tile
    (host f64 handles it); value-free funcs still run."""
    # distinct seed: the tile fingerprint keys on (name, count, last ts)
    # and would otherwise collide with the small-base variant's tile
    series = _series(resets=True, seed=11)  # base 1e9, resets to ~1e6
    for func in ("rate", "increase"):
        rows = try_rollup_tpu(engine, func, series, CFG, ())
        assert rows is not None, func
        _assert_close(np.stack(rows), _host_rows(func, series), 1e-6,
                      f"{func} on a wide tile")
    for func in ("delta", "min_over_time"):
        assert try_rollup_tpu(engine, func, series, CFG, ()) is None, func
    gids = np.zeros(len(series), np.int32)
    out = try_aggr_rollup_tpu(engine, "sum", "rate", series, gids, 1, CFG)
    assert out is not None
    host = _host_rows("rate", series)
    _assert_close(out[0], np.where(np.isnan(host).all(axis=0), np.nan,
                                   np.nansum(host, axis=0)),
                  1e-6, "sum(rate) on a wide tile")
    # value-free funcs are immune to value error: stay on device
    rows = try_rollup_tpu(engine, "count_over_time", series, CFG, ())
    assert rows is not None
    _assert_close(np.stack(rows), _host_rows("count_over_time", series),
                  1e-9, "count on wide-range tile")


def test_fractional_scale_wide_mantissa_falls_back(engine):
    """Value range < 2^24 but MANTISSA range >= 2^24 (fractional decimal
    scale 10^-3): the one f32 rounding happens on the rebased mantissa, so
    the value-space gate alone would silently cost integer exactness for
    equality-sensitive funcs. The tile is wide, so its value plane holds
    increments taken in f64 (each 100-200, rounded once to f32, with the
    reset classification in f64): rate runs there and equals the host's
    rows; the other value-dependent funcs must refuse the tile; value-free
    funcs still run (round-4 advisor finding)."""
    rng = np.random.default_rng(21)
    n = 140
    series = []
    for i in range(8):
        ts = np.arange(n, dtype=np.int64) * 15_000 + START
        # 3-decimal counter reaching ~21k: mantissa range ~2.1e7 > 2^24,
        # value range far below 2^24
        v = np.round(np.cumsum(rng.uniform(100.0, 200.0, n)), 3)
        mn = MetricName.from_dict({"__name__": "frac", "i": str(i)})
        series.append(SeriesData(mn, ts, v, raw_name=mn.marshal()))
    for func in ("changes", "delta"):
        assert try_rollup_tpu(engine, func, series, CFG, ()) is None, func
    rows = try_rollup_tpu(engine, "rate", series, CFG, ())
    assert rows is not None
    _assert_close(np.stack(rows), _host_rows("rate", series), 1e-6,
                  "rate on wide-mantissa tile")
    rows = try_rollup_tpu(engine, "count_over_time", series, CFG, ())
    assert rows is not None
    _assert_close(np.stack(rows), _host_rows("count_over_time", series),
                  1e-9, "count on wide-mantissa tile")


# -- affine funcs get per-series f64 addback -------------------------------

@pytest.mark.parametrize("func", ["min_over_time", "max_over_time",
                                  "avg_over_time", "first_over_time",
                                  "last_over_time", "default_rollup"])
def test_affine_addback_large_base(engine, func):
    series = _series()
    rows = try_rollup_tpu(engine, func, series, CFG, ())
    assert rows is not None, "affine funcs run via host addback"
    host = _host_rows(func, series)
    # addback restores absolute scale in f64; residual error is the f32
    # rounding of the rebased part relative to the ABSOLUTE value — tiny
    _assert_close(np.stack(rows), host, 1e-6, func)


# -- gating: what f32 tiles must NOT run -----------------------------------

def test_f32_gating(engine):
    series = _series(n_series=8)
    # sum_over_time needs n*v0 — not affine, must fall back
    assert try_rollup_tpu(engine, "sum_over_time", series, CFG, ()) is None
    # fused aggregation crosses series with different v0: affine funcs
    # cannot run fused
    gids = np.zeros(len(series), np.int32)
    assert try_aggr_rollup_tpu(engine, "sum", "last_over_time", series,
                               gids, 1, CFG) is None
    # topk selection compares absolutes across series
    assert try_topk_rollup_tpu(engine, "topk", 3.0, "max_over_time",
                               series, CFG) is None
    # f64 engines are unrestricted
    e64 = TPUEngine(value_dtype=np.float64, min_series=2)
    assert e64.func_mode("sum_over_time", per_series=False) == "direct"


def test_fused_aggr_rate_large_base(engine):
    """The headline shape: sum by (g)(rate(counter)) fused on f32 tiles."""
    series = _series(n_series=96)
    gids = np.array([i % 5 for i in range(len(series))], np.int32)
    out = try_aggr_rollup_tpu(engine, "sum", "rate", series, gids, 5, CFG)
    assert out is not None
    host_rows = _host_rows("rate", series)
    T = host_rows.shape[1]
    expect = np.zeros((5, T))
    for g in range(5):
        sub = host_rows[gids == g]
        expect[g] = np.where(np.isnan(sub).all(axis=0), np.nan,
                             np.nansum(sub, axis=0))
    _assert_close(out, expect, 1e-5, "sum(rate) fused")


def test_quantile_rate_large_base(engine):
    from victoriametrics_tpu.query.tpu_engine import group_slots
    series = _series(n_series=48, seed=5)
    gids = np.array([i % 3 for i in range(len(series))], np.int32)
    slots, max_group = group_slots(gids, 3)
    out = try_quantile_rollup_tpu(engine, 0.5, "rate", series, gids, 3,
                                  CFG, slots, max_group)
    assert out is not None
    host_rows = _host_rows("rate", series)
    T = host_rows.shape[1]
    expect = np.full((3, T), np.nan)
    for g in range(3):
        sub = host_rows[gids == g]
        for t in range(T):
            col = sub[:, t]
            if not np.isnan(col).all():
                expect[g, t] = np.nanquantile(col, 0.5)
    _assert_close(out, expect, 1e-5, "median(rate) fused")


def test_auto_dtype_on_cpu():
    # this suite runs on the CPU backend (conftest pins it): auto = f64
    assert np.dtype(tpu_engine.auto_value_dtype()) == np.float64
    assert np.dtype(TPUEngine().value_dtype) == np.float64
