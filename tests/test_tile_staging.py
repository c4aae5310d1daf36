"""The cold tile build's batched staging (ops/device_decode.stage_rows:
one grouped float -> decimal pass, one native pack, its NumPy twin where
the library is missing) against the per-row statement it replaced, kept
here as the oracle: per-series float_to_decimal, the per-row delta-plane
loop, the per-row v0 and wide-range gates. Field by field, dtype
included, plus v0 and risky, on f32 (rebased) and f64 engines.

The one deliberate difference: the old loop tested |m| < 2^31 with
np.abs, which leaves abs(INT64_MIN) -- the plain-NaN sentinel --
negative, so a row of nothing but NaNs packed as zeros and the device
read 0 where the host reads NaN. The oracle tests the range, as the
batched pass does, and such a row goes to the dense tile
(test_an_all_nan_row_goes_dense)."""

import zlib

import numpy as np
import pytest

from victoriametrics_tpu import native
from victoriametrics_tpu.ops import decimal as dec
from victoriametrics_tpu.ops import device_decode as dd
from victoriametrics_tpu.ops.rollup_np import RollupConfig
from victoriametrics_tpu.query.tpu_engine import (F32_SAFE_RANGE, TPUEngine,
                                                  try_aggr_rollup_tpu)
from victoriametrics_tpu.storage.metric_name import MetricName
from victoriametrics_tpu.storage.storage import SeriesData
from victoriametrics_tpu.utils import metrics as metricslib

START = 1_753_700_000_000
BIG = 2 ** 31


def _big(x):
    return x <= -BIG or x >= BIG


def _old_pack(series, start_ms, value_dtype, rebase):
    """pack_delta_planes as it was: one row at a time."""
    S = len(series)
    counts = np.array([len(t) for t, _, _ in series], dtype=np.int32)
    if (counts < 1).any():
        return None
    N = int(counts.max())
    ts_first, ts_fd, val_first, val_fd = (np.zeros(S, np.int64)
                                          for _ in range(4))
    scale = np.ones(S, dtype=value_dtype)
    ts_d2 = np.zeros((S, max(N - 2, 1)), dtype=np.int64)
    val_d2 = np.zeros((S, max(N - 2, 1)), dtype=np.int64)
    for i, (ts, m, exp) in enumerate(series):
        rel = np.asarray(ts, dtype=np.int64) - start_ms
        m = np.asarray(m, dtype=np.int64)
        if _big(rel.min()) or _big(rel.max()) or _big(m.min()) or \
                _big(m.max()):
            return None
        if rebase and (_big((m - m[0]).min()) or _big((m - m[0]).max())):
            return None
        ts_first[i] = rel[0]
        val_first[i] = m[0]
        scale[i] = np.float64(10.0) ** exp
        if rel.size >= 2:
            td, vd = np.diff(rel), np.diff(m)
            if np.abs(td).max() >= BIG or np.abs(vd).max() >= BIG:
                return None
            ts_fd[i] = td[0]
            val_fd[i] = vd[0]
            if rel.size >= 3:
                t2, v2 = np.diff(td), np.diff(vd)
                if np.abs(t2).max() >= BIG or np.abs(v2).max() >= BIG:
                    return None
                ts_d2[i, :t2.size] = t2
                val_d2[i, :v2.size] = v2

    def narrowest(d2):
        m = np.abs(d2).max()
        return np.int8 if m < 127 else np.int16 if m < 32767 else np.int32
    return dd.DeltaPlanes(
        ts_first=ts_first.astype(np.int32), ts_fdelta=ts_fd.astype(np.int32),
        ts_d2=ts_d2.astype(narrowest(ts_d2)),
        val_first=val_first.astype(np.int32),
        val_fdelta=val_fd.astype(np.int32),
        val_d2=val_d2.astype(narrowest(val_d2)), scale=scale, counts=counts)


def _old_stage(ts_rows, val_rows, start_ms, value_dtype, f32):
    """_build_tiles' staging as it was: (planes, v0, risky)."""
    v0 = risky = None
    if f32:
        v0 = np.array([v[0] if v.size and np.isfinite(v[0]) else 0.0
                       for v in val_rows], dtype=np.float64)
        risky = any(
            v.size and np.isfinite(v).any() and
            float(np.nanmax(np.abs(np.where(np.isfinite(v), v, v0[i]) -
                                   v0[i]))) >= F32_SAFE_RANGE
            for i, v in enumerate(val_rows))
    triples = [(t, *dec.float_to_decimal(v))
               for t, v in zip(ts_rows, val_rows)]
    if f32 and not risky:
        for _, m, _ in triples:
            if not m.size:
                continue
            sane = (m > -BIG) & (m < BIG)
            if not sane.any():
                continue
            base = m[0] if sane[0] else m[sane][0]
            if float(np.abs(m[sane] - base).max()) >= F32_SAFE_RANGE:
                risky = True
                break
    planes = _old_pack(triples, start_ms, value_dtype, f32)
    if planes is not None and f32:
        v0 = np.array([float(m[0]) if m.size else 0.0
                       for _, m, _ in triples], dtype=np.float64) * \
            np.array([10.0 ** e for _, _, e in triples])
        v0[~np.isfinite(v0)] = 0.0
    return planes, v0, bool(risky)


def _ts(rng, n, jitter=2000):
    ts = START + np.arange(n, dtype=np.int64) * 15_000 + \
        rng.integers(-jitter, jitter, n)
    ts.sort()
    return ts


def _counters(rng, lens, base=0.0):
    return [base + np.cumsum(rng.integers(0, 50, n)).astype(np.float64)
            for n in lens]


def _case(name, rng):
    """(timestamp rows, value rows) for one named case."""
    lens = [140] * 24
    if name == "short_rows":
        lens = [1, 2, 3, 8, 9, 1, 2, 3, 8, 140, 70, 33]
        vals = _counters(rng, lens)
    elif name == "short_17_digit":
        lens = [1, 2, 3, 8, 9, 5, 8, 16]
        vals = [rng.standard_normal(n) / 3.0 for n in lens]
    elif name == "unequal":
        lens = list(rng.integers(3, 400, 40))
        vals = _counters(rng, lens, base=1e6)
    elif name == "gauges_2dp":
        vals = [np.round(rng.uniform(0, 100, n), 2) for n in lens]
    elif name == "digits_17":
        vals = [rng.uniform(0, 1, n) * (2.0 / 3.0) for n in lens]
    elif name == "nan_stale_inf":
        vals = _counters(rng, lens)
        vals[3][7] = np.nan
        vals[5][0] = dec.STALE_NAN
        vals[9][50] = np.inf
        vals[11][-1] = -np.inf
    elif name == "stale_only":
        vals = _counters(rng, lens)
        vals[2][:] = dec.STALE_NAN
    elif name == "d1_over_int32":
        # every mantissa fits int32, one first difference does not
        vals = _counters(rng, lens)
        vals[4][60:62] = -2.0e9, 2.0e9
    elif name == "d2_over_int32":
        # a spike: both first differences fit int32, the second does not
        vals = _counters(rng, lens)
        vals[6][80] = 2147483000.0
    elif name == "rebase_over_int32":
        # every mantissa and difference fits int32, m - m[0] does not
        vals = _counters(rng, lens)
        vals[1] = np.linspace(-2.0e9, 2.0e9, lens[1]).round()
    elif name == "value_risky":
        vals = _counters(rng, lens)
        vals[7][100:] += 2.0e7      # |v - v0| over 2^24, mantissas fine
    elif name == "mantissa_risky":
        # 3-decimal gauges spread 2e4 apart: |v - v0| stays under 2^24,
        # the rebased mantissas (x 1000) do not
        vals = [np.round(rng.uniform(0, 1, n), 3) for n in lens]
        vals[8] = np.round(np.linspace(0.001, 20000.0, lens[8]), 3)
    elif name == "large_base":
        vals = _counters(rng, lens, base=1.0e9)
    else:
        raise AssertionError(name)
    return [_ts(rng, n) for n in lens], vals


CASES = ["short_rows", "short_17_digit", "unequal", "gauges_2dp",
         "digits_17", "nan_stale_inf", "stale_only", "d1_over_int32",
         "d2_over_int32", "rebase_over_int32", "value_risky",
         "mantissa_risky", "large_base"]


@pytest.fixture(params=["native", "python"])
def path(request, monkeypatch):
    if request.param == "native":
        if not native.available():
            pytest.skip("native library not built here")
    else:
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load", lambda: None)
    return request.param


def _assert_same(got, want, label):
    planes, v0, risky = want
    if planes is None:
        assert got.planes is None, label
    else:
        assert got.planes is not None, label
        for f in ("ts_first", "ts_fdelta", "ts_d2", "val_first",
                  "val_fdelta", "val_d2", "scale", "counts"):
            a, b = getattr(got.planes, f), getattr(planes, f)
            assert a.dtype == b.dtype, (label, f, a.dtype, b.dtype)
            assert a.shape == b.shape, (label, f)
            assert a.tobytes() == b.tobytes(), (label, f)
    if v0 is None:
        assert got.v0 is None, label
    else:
        assert got.v0.dtype == np.float64
        assert got.v0.tobytes() == v0.tobytes(), label
    assert got.risky == risky, label


@pytest.mark.parametrize("f32", [True, False], ids=["f32", "f64"])
@pytest.mark.parametrize("case", CASES)
def test_staging_matches_the_per_row_statement(case, f32, path):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    ts_rows, val_rows = _case(case, rng)
    dtype = np.float32 if f32 else np.float64
    want = _old_stage(ts_rows, val_rows, START, dtype, f32)
    got = dd.stage_rows(ts_rows, val_rows, START, dtype, rebase=f32,
                        gate=F32_SAFE_RANGE)
    assert got.path == path
    _assert_same(got, want, (case, f32, path))


# what each case must exercise, so a generator change cannot quietly
# turn a refusal or a gate into an accepted plain case
EXPECT = {"nan_stale_inf": (None, None), "stale_only": (None, None),
          "d1_over_int32": (None, None), "d2_over_int32": (None, None),
          "digits_17": (None, None), "short_17_digit": (None, None),
          "rebase_over_int32": (None, "planes64"),
          "value_risky": ("risky", "planes64"),
          "mantissa_risky": ("risky", "planes64"),
          "short_rows": ("planes", "planes64"),
          "large_base": ("planes", "planes64")}


@pytest.mark.parametrize("case", sorted(EXPECT))
def test_each_case_takes_the_branch_it_names(case):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    ts_rows, val_rows = _case(case, rng)
    f32_want, f64_want = EXPECT[case]
    p32, _, risky = _old_stage(ts_rows, val_rows, START, np.float32, True)
    p64, _, _ = _old_stage(ts_rows, val_rows, START, np.float64, False)
    if f32_want is None:
        assert p32 is None
    elif f32_want == "risky":
        assert p32 is not None and risky
    else:
        assert p32 is not None and not risky
    assert (p64 is not None) == (f64_want == "planes64")


def test_an_all_nan_row_goes_dense(path):
    rng = np.random.default_rng(5)
    ts_rows = [_ts(rng, 50) for _ in range(4)]
    val_rows = _counters(rng, [50] * 4)
    val_rows[2][:] = np.nan
    got = dd.stage_rows(ts_rows, val_rows, START, np.float64)
    assert got.planes is None


def test_wide_delta_planes_keep_int32():
    """A plane narrows by its largest |d2| over every row: int8, int16 or
    int32, as the per-row pack chose."""
    rng = np.random.default_rng(9)
    ts_rows = [_ts(rng, 64, jitter=5) for _ in range(3)]
    val_rows = [np.cumsum(rng.integers(0, 3, 64)).astype(np.float64),
                np.cumsum(rng.integers(0, 300, 64)).astype(np.float64),
                np.cumsum(rng.integers(0, 3, 64)).astype(np.float64)]
    got = dd.stage_rows(ts_rows, val_rows, START, np.float64).planes
    assert got.ts_d2.dtype == np.int8 and got.val_d2.dtype == np.int16
    val_rows[1] = np.cumsum(rng.integers(0, 90_000, 64)).astype(np.float64)
    got = dd.stage_rows(ts_rows, val_rows, START, np.float64).planes
    assert got.val_d2.dtype == np.int32


def test_empty_row_refuses_but_gates_still_read_every_row(path):
    rng = np.random.default_rng(11)
    ts_rows = [_ts(rng, 40), np.zeros(0, np.int64), _ts(rng, 40)]
    val_rows = [np.cumsum(rng.integers(0, 5, 40)).astype(np.float64),
                np.zeros(0), np.linspace(0, 3e7, 40).round()]
    want = _old_stage(ts_rows, val_rows, START, np.float32, True)
    got = dd.stage_rows(ts_rows, val_rows, START, np.float32, rebase=True,
                        gate=F32_SAFE_RANGE)
    assert want[0] is None and want[2]
    _assert_same(got, want, "empty row")


# -- the counter: vm_device_tile_build_rows_total{path=} --------------------

CFG = RollupConfig(start=START + 600_000, end=START + 1_800_000,
                   step=60_000, window=300_000)


def _series(n_series, base=0.0, step=50, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_series):
        ts = _ts(rng, 140)
        v = base + np.cumsum(rng.integers(0, step, 140)).astype(np.float64)
        mn = MetricName.from_dict({"__name__": "m", "i": str(i)})
        out.append(SeriesData(mn, ts, v, raw_name=mn.marshal()))
    return out


def _rows_by_path():
    return {p: metricslib.REGISTRY.counter(metricslib.format_name(
        "vm_device_tile_build_rows_total", {"path": p})).get()
        for p in ("native", "python", "dense")}


def _cold_sum(series):
    before = _rows_by_path()
    eng = TPUEngine(min_series=2)
    gids = np.zeros(len(series), np.int32)
    out = try_aggr_rollup_tpu(eng, "sum", "rate", series, gids, 1, CFG)
    assert out is not None
    after = _rows_by_path()
    return {p: after[p] - before[p] for p in after}


@pytest.mark.parametrize("forced_off", [False, True],
                         ids=["library", "no_library"])
def test_cold_build_books_its_rows(forced_off, monkeypatch):
    if forced_off:
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load", lambda: None)
    elif not native.available():
        pytest.skip("native library not built here")
    moved = _cold_sum(_series(12))
    path = "python" if forced_off else "native"
    assert moved == {"native": 0, "python": 0, "dense": 0, path: 12}


def test_a_plan_past_int32_books_dense():
    # increments of up to 3e9: first differences past int32
    moved = _cold_sum(_series(10, step=3_000_000_000, seed=4))
    assert moved == {"native": 0, "python": 0, "dense": 10}


def test_counter_registered_at_import():
    text = metricslib.REGISTRY.write_prometheus()
    for p in ("native", "python", "dense"):
        assert f'vm_device_tile_build_rows_total{{path="{p}"}}' in text
