"""Wide float32 tiles hold reset-corrected increments (tpu_engine.V0Info).

A float32 tile whose rebased range reaches 2^24 carries, in its value
plane, each row's increments: taken in f64 on the host with upstream's
removeCounterResets rule (a drop under 1/8 of the value adds 0, a larger
one the value after it) and rounded once to f32. The counter family runs
on them on the device; everything here is held to rollup_np's f64 rows at
rtol 1e-6, where these data read at most some 3e-7 (one f32 rounding of
each increment, a window sum of about 20 of them, the answer's own f32
rounding):

(a) per-series rate, increase, increase_pure and irate of byte counters
    (bases 1e9-1e14, 1e4-1e8 B/s) with a full and a partial reset inside
    the bulk, and a counter born inside the tile's first window, whose
    increase reads the new-series baseline; rate and irate over a 6 h
    grid, whose timestamps pass f32's exact integers;
(b) the cold fused panel and the resident window after several appends
    (a full and a partial reset exactly at an append seam) and one slide
    (a reset at a row's first retained column), with
    vm_device_wide_rows_total{path="device"} growing by the live rows
    exactly once a query on the cold and the resident path;
(c) delta and max_over_time on the same tile still decline and count
    {path="host"};
(d) a narrow tile stages and launches what it did before increments
    existed: its delta planes, the rebased decode, the kernels' calls.
"""

import numpy as np
import pytest

from victoriametrics_tpu.ops import device_decode as dd
from victoriametrics_tpu.ops import device_rollup
from victoriametrics_tpu.ops import rollup_np
from victoriametrics_tpu.ops.rollup_np import RollupConfig
from victoriametrics_tpu.query import tpu_engine as te
from victoriametrics_tpu.query.exec import exec_query
from victoriametrics_tpu.query.types import EvalConfig
from victoriametrics_tpu.storage.metric_name import MetricName
from victoriametrics_tpu.storage.storage import SeriesData, Storage
from victoriametrics_tpu.utils import fasttime
from victoriametrics_tpu.utils import metrics as metricslib

START = 1_753_700_000_000
CFG = RollupConfig(start=START + 600_000, end=START + 1_800_000,
                   step=60_000, window=300_000)
STEP, SCRAPE = 60_000, 15_000
RTOL = 1e-6
COUNTERS = ("rate", "increase", "increase_pure", "irate")
WIDE = "vm_device_wide_rows_total"


def _wide(path: str) -> float:
    return metricslib.REGISTRY.counter(
        metricslib.format_name(WIDE, {"path": path})).get()


def _byte_rows(rng, n_rows: int, n: int):
    """[n_rows, n] whole-byte counters: bases 1e9-1e14, rates 1e4-1e8 B/s
    log-uniform, each scrape adding rate x 15 s x U[0.5, 1.5)."""
    rate = np.exp(rng.uniform(np.log(1e4), np.log(1e8), n_rows))
    base = np.round(np.exp(rng.uniform(np.log(1e9), np.log(1e14), n_rows)))
    inc = np.round(rate[:, None] * 15 * rng.uniform(0.5, 1.5, (n_rows, n)))
    return base[:, None] + np.cumsum(inc, axis=1)


def _full_reset(v: np.ndarray, at: int) -> None:
    """A reboot at sample `at`: the counter restarts from what it counted
    since."""
    v[at:] -= v[at - 1] - 1000.0


def _partial_reset(v: np.ndarray, at: int) -> None:
    """A drop under 1/8 of the value at sample `at`."""
    v[at:] -= np.round(0.05 * v[at - 1]) + (v[at] - v[at - 1])


def _assert_close(got, want, label: str) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    assert got.shape == want.shape, label
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want),
                                  err_msg=label)
    m = ~np.isnan(want)
    err = np.abs(got[m] - want[m]) / np.maximum(np.abs(want[m]), 1e-3)
    worst = float(err.max(initial=0.0))
    assert worst < RTOL, f"{label}: rel err {worst:.3g}"


@pytest.fixture(scope="module")
def engine():
    return te.TPUEngine(value_dtype=np.float32, min_series=2)


def _series(seed: int):
    """48 wide byte counters over 35 min at jittered 15 s scrapes: row 5
    reboots, row 11 drops under 1/8, row 3 is born inside the tile's first
    window at 5 bytes."""
    rng = np.random.default_rng(seed)
    n = 140
    vals = _byte_rows(rng, 48, n)
    _full_reset(vals[5], 70)
    _partial_reset(vals[11], 90)
    out = []
    for i in range(48):
        ts = np.arange(n, dtype=np.int64) * SCRAPE + START + \
            rng.integers(-2000, 2001, n)
        ts.sort()
        v = vals[i]
        if i == 3:
            ts, v = ts[45:], v[45:] - v[45] + 5.0
        mn = MetricName.from_dict({"__name__": "bytes", "i": str(i)})
        out.append(SeriesData(mn, ts, v, raw_name=mn.marshal()))
    return out


def _host_rows(func, series):
    return rollup_np.rollup_batch(
        func, [(sd.timestamps, sd.values) for sd in series], CFG)


@pytest.mark.parametrize("func", COUNTERS)
def test_the_counter_family_equals_the_f64_rows(engine, func):
    series = _series(45)
    rows = te.try_rollup_tpu(engine, func, series, CFG, ())
    assert rows is not None, func
    tiles = engine.cache().get(te._fingerprint(series, CFG.start))
    assert tiles[3].wide_range and tiles[3].increments
    _assert_close(np.stack(rows), _host_rows(func, series), func)


def test_increase_reads_the_born_baseline_in_the_first_window(engine):
    """Row 3 starts at 5 bytes inside the grid's first windows: with no
    sample before the window the counter counts from 0 (the host's
    _new_series_base), so increase there is the row's value, not its
    growth since the first sample."""
    series = _series(45)
    rows = te.try_rollup_tpu(engine, "increase", series, CFG, ())
    host = _host_rows("increase", series)[3]
    ts, v = series[3].timestamps, series[3].values
    grid = CFG.out_timestamps()
    born = [j for j, t in enumerate(grid)
            if ts[0] > t - CFG.window and ts[0] <= t]
    assert born
    for j in born:
        last = v[np.searchsorted(ts, grid[j], side="right") - 1]
        assert host[j] == last   # the baseline the branch must reproduce
    _assert_close(rows[3], host, "born increase")


@pytest.mark.parametrize("func", ["rate", "irate"])
def test_a_six_hour_grid_keeps_its_time_spans(engine, func):
    """Over a 6 h grid the timestamps pass 2^24 ms, where f32 spaces them
    2 ms apart: the time spans are taken as int32 differences, so rate
    stays at the increments' own precision (a float32 span read 7e-6)."""
    rng = np.random.default_rng(6)
    n, rows = 1460, 16
    vals = _byte_rows(rng, rows, n)
    series = []
    for i in range(rows):
        ts = np.arange(n, dtype=np.int64) * SCRAPE + START + \
            rng.integers(-2000, 2001, n)
        ts.sort()
        mn = MetricName.from_dict({"__name__": "long", "i": str(i)})
        series.append(SeriesData(mn, ts, vals[i], raw_name=mn.marshal()))
    cfg = RollupConfig(start=START + 600_000, end=START + 600_000 +
                       360 * STEP, step=STEP, window=300_000)
    rows_dev = te.try_rollup_tpu(engine, func, series, cfg, ())
    host = rollup_np.rollup_batch(
        func, [(sd.timestamps, sd.values) for sd in series], cfg)
    _assert_close(np.stack(rows_dev), host, f"{func} over 6 h")


def test_counter_increments_follow_the_reset_rule():
    """The host's pass against remove_counter_resets' own differences, a
    row's first sample adding 0, or its increment over `prev`."""
    v = np.array([10.0, 20.0, 15.0, 30.0, 1.0, 5.0, 100.0, 99.0])
    inc, last = dd.counter_increments(v, np.array([3, 5]))
    corr = np.concatenate([rollup_np.remove_counter_resets(v[:3]),
                           rollup_np.remove_counter_resets(v[3:])])
    want = np.diff(corr, prepend=np.nan)
    want[[0, 3]] = 0.0
    np.testing.assert_array_equal(inc, want)
    np.testing.assert_array_equal(last, [15.0, 99.0])
    inc, last = dd.counter_increments(v[3:], np.array([5]),
                                      np.array([40.0]))
    assert inc[0] == 30.0        # a drop under 1/8 of 40 would add 0
    inc, _ = dd.counter_increments(np.array([36.0]), np.array([1]),
                                   np.array([40.0]))
    assert inc[0] == 0.0
    inc, last = dd.counter_increments(np.zeros(0), np.array([0]),
                                      np.array([7.0]))
    assert inc.size == 0 and last[0] == 7.0


# -- the served path: cold fused panel, resident window, declines ----------

Q = "sum by (g)(rate(nb[5m]))"
ROWS = 24


class _Fleet:
    """24 wide byte counters in 6 groups in a Storage: a bulk of 160
    regular 15 s scrapes ending at t0, then ticks of fresh scrapes."""

    def __init__(self, path):
        self.t0 = (fasttime.unix_ms() - 3 * 3_600_000) // STEP * STEP
        self.rng = np.random.default_rng(4500)
        self.rate = np.exp(self.rng.uniform(np.log(1e4), np.log(1e8), ROWS))
        self.store = Storage(str(path))
        self.last = None
        self.ts, self.vals = [], []     # everything written, tick by tick

    def write(self, t_from: int, k: int, edit=None):
        """k scrapes after t_from; `edit(vals, ts)` may reset rows."""
        ts = t_from + SCRAPE * np.arange(1, k + 1)
        inc = np.round(self.rate[:, None] * 15 *
                       self.rng.uniform(0.5, 1.5, (ROWS, k)))
        if self.last is None:
            base = np.round(np.exp(self.rng.uniform(np.log(1e9),
                                                    np.log(1e14), ROWS)))
            vals = base[:, None] + np.cumsum(inc, axis=1)
        else:
            vals = self.last[:, None] + np.cumsum(inc, axis=1)
        if edit is not None:
            edit(vals, ts)
        self.last = vals[:, -1].copy()
        self.ts.append(ts)
        self.vals.append(vals)
        rows = []
        for i in range(ROWS):
            lab = {"__name__": "nb", "i": str(i), "g": f"g{i % 6}"}
            rows += [(lab, int(t), float(x)) for t, x in zip(ts, vals[i])]
        self.store.add_rows(rows)

    def query(self, engine, end: int, q: str = Q):
        got = exec_query(EvalConfig(start=end - 20 * STEP, end=end,
                                    step=STEP, storage=self.store,
                                    tpu=engine), q)
        return {r.metric_name.marshal(): np.asarray(r.values) for r in got}

    def check(self, engine, end: int, q: str = Q) -> None:
        got, want = self.query(engine, end, q), self.query(None, end, q)
        assert set(got) == set(want) and got
        for k in got:
            _assert_close(got[k], want[k], f"{q} at {end}")


@pytest.fixture
def fleet(tmp_path):
    f = _Fleet(tmp_path / "s")
    try:
        yield f
    finally:
        f.store.close()


def test_the_resident_window_equals_the_f64_rows(fleet, monkeypatch):
    """Cold fused panel, three ticks (a full and a partial reset exactly
    at the second tick's seam), then a tick of 25 minutes that slides the
    window: its first retained column of row 7 is a reset. Every answer
    equals the host's; the device counts the 24 rows once a query, the
    host none; the window is hit on every tick and slides once; after the
    slide the offsets plus a row's increments are still its corrected
    counter."""
    t0 = fleet.t0
    # the slide's cutoff: the big tick's fetch bound, end - 20 steps - 5 m
    # of window - 5 m of lookback delta
    end_big = t0 + 28 * STEP
    cutoff = end_big - 20 * STEP - 600_000

    def reset_at_cutoff(vals, ts):
        _full_reset(vals[7], int(np.searchsorted(ts, cutoff)))
        _full_reset(vals[2], 40)
        _partial_reset(vals[9], 100)

    fleet.write(t0 - 160 * SCRAPE, 160, reset_at_cutoff)
    fleet.store.force_flush()
    engine = te.TPUEngine(value_dtype=np.float32, min_series=4)
    slid = []
    compact = te.compact_window

    def spy(eng, rt, cutoff_abs):
        ok = compact(eng, rt, cutoff_abs)
        slid.append((rt, ok))
        return ok

    monkeypatch.setattr(te, "compact_window", spy)
    hits = metricslib.REGISTRY.counter("vm_device_window_cache_hits_total")
    m0, h0 = (_wide("device"), _wide("host")), hits.get()
    fleet.check(engine, t0)
    assert (_wide("device"), _wide("host")) == (m0[0] + ROWS, m0[1])

    def seam(vals, ts):
        vals[4] -= fleet.last[4] - 500.0            # a reboot at the seam
        vals[8] -= np.round(0.05 * fleet.last[8]) + \
            (vals[8, 0] - fleet.last[8])            # a partial reset there

    for tick in range(1, 4):
        fleet.write(t0 + (tick - 1) * STEP, 4, seam if tick == 2 else None)
        fleet.check(engine, t0 + tick * STEP)
    assert hits.get() - h0 == 3
    assert not slid
    fleet.write(t0 + 3 * STEP, 100)
    fleet.check(engine, end_big)
    assert hits.get() - h0 == 4
    assert len(slid) == 1 and slid[0][1]
    # five queries on the device, 24 rows each, none on the host
    assert (_wide("device"), _wide("host")) == (m0[0] + 5 * ROWS, m0[1])
    rt = slid[0][0]
    v0 = rt.tiles[3]
    assert v0.increments and rt.base_ms == cutoff
    # the tile's rows are in the fetch's order: match them by last value
    order = np.argsort(fleet.last)
    row_of = order[np.searchsorted(fleet.last[order], v0.last)]
    np.testing.assert_array_equal(v0.last, fleet.last[row_of])
    # offsets + the retained increments against the host's corrected
    # counter over everything the tile held since its cold build, which
    # fetched from the first query's bound
    inc = np.asarray(rt.tiles[1], np.float64)
    got = v0.offsets + np.array([inc[r, :rt.counts_host[r]].sum()
                                 for r in range(ROWS)])
    ts, vals = np.concatenate(fleet.ts), np.concatenate(fleet.vals, axis=1)
    held = vals[row_of][:, ts >= t0 - 20 * STEP - 600_000]
    want = rollup_np.remove_counter_resets(held)[:, -1]
    np.testing.assert_allclose(got, want, rtol=RTOL)


def test_delta_and_max_over_time_still_decline_to_the_host(fleet):
    """The same wide tile under delta (fused) and max_over_time (the
    per-series entry): both decline, their 24 rows counted on the host
    once a query, none on the device, and the host's answers come back."""
    fleet.write(fleet.t0 - 160 * SCRAPE, 160)
    fleet.store.force_flush()
    engine = te.TPUEngine(value_dtype=np.float32, min_series=4)
    for q in ("sum by (g)(delta(nb[5m]))", "max_over_time(nb[5m])"):
        d0, h0 = _wide("device"), _wide("host")
        got = fleet.query(engine, fleet.t0, q)
        assert (_wide("device"), _wide("host")) == (d0, h0 + ROWS), q
        want = fleet.query(None, fleet.t0, q)
        assert set(got) == set(want) and got
        for k in got:
            np.testing.assert_array_equal(got[k], want[k])


def test_a_narrow_tile_stages_and_launches_what_it_did(monkeypatch):
    """A counter whose rebased range stays under 2^24: no increments pass
    runs, the delta planes go up as the staging made them (and nothing
    else), the decode gives the rebased values, and the fused kernel is
    the same jitted function, called as before increments existed."""
    rng = np.random.default_rng(7)
    n = 140
    series = []
    for i in range(16):
        ts = np.arange(n, dtype=np.int64) * SCRAPE + START
        v = 1e9 + np.cumsum(rng.integers(0, 50, n)).astype(np.float64)
        mn = MetricName.from_dict({"__name__": "narrow", "i": str(i)})
        series.append(SeriesData(mn, ts, v, raw_name=mn.marshal()))

    def no_pass(*a, **k):
        raise AssertionError("the increments pass ran on a narrow tile")

    monkeypatch.setattr(dd, "counter_increments", no_pass)
    from victoriametrics_tpu.models import tile_cache
    put, launched = [], []
    real_put, real_call = tile_cache.chunked_device_put, te.timed_kernel_call
    monkeypatch.setattr(tile_cache, "chunked_device_put",
                        lambda a, *r: put.append(a) or real_put(a, *r))
    monkeypatch.setattr(te, "timed_kernel_call",
                        lambda name, fn, *a, **k:
                        launched.append((fn, k)) or real_call(name, fn,
                                                              *a, **k))
    engine = te.TPUEngine(value_dtype=np.float32, min_series=2)
    gids = np.zeros(len(series), np.int32)
    out = te.try_aggr_rollup_tpu(engine, "sum", "rate", series, gids, 1, CFG)
    assert out is not None
    staged = dd.stage_rows([s.timestamps for s in series],
                           [s.values for s in series], CFG.start,
                           np.float32, rebase=True, gate=te.F32_SAFE_RANGE)
    assert staged.planes is not None and not staged.risky
    import dataclasses
    fields = [f.name for f in dataclasses.fields(staged.planes)]
    assert len(put) == len(fields)
    for a, f in zip(put, fields):
        want = getattr(staged.planes, f)
        assert a.dtype == want.dtype, f
        if a.ndim == 2:   # the d2 planes carry the headroom columns
            assert a.shape[1] >= want.shape[1]
            np.testing.assert_array_equal(a[:, :want.shape[1]], want)
            assert not a[:, want.shape[1]:].any()
        else:
            np.testing.assert_array_equal(a, want)
    tiles = engine.cache().get(te._fingerprint(series, CFG.start))
    assert not tiles[3].wide_range and not tiles[3].increments
    v = np.asarray(tiles[1])[:, :n]
    np.testing.assert_array_equal(
        v, np.stack([(s.values - s.values[0]).astype(np.float32)
                     for s in series]))
    assert [(fn, k) for fn, k in launched] == [
        (device_rollup.rollup_aggregate_tile, {})]


def _declines(reason: str) -> float:
    return metricslib.REGISTRY.counter(metricslib.format_name(
        "vm_device_roll_declines_total", {"reason": reason})).get()


def test_a_wide_tile_with_a_nan_keeps_its_rebased_values(engine):
    """A wide tile holding a non-finite value carries no increments: the
    counter family declines it, as every value function did before."""
    series = _series(46)
    series[8].values[30] = np.nan
    assert te.try_rollup_tpu(engine, "rate", series, CFG, ()) is None
    tiles = engine.cache().get(te._fingerprint(series, CFG.start))
    assert tiles[3].wide_range and not tiles[3].increments


def test_a_nan_in_a_tail_declines_the_window(fleet):
    """A tick carrying a NaN bound for an increments tile: the advance
    declines with its reason counted once, and the answer is the host's."""
    t0 = fleet.t0
    fleet.write(t0 - 160 * SCRAPE, 160)
    fleet.store.force_flush()
    engine = te.TPUEngine(value_dtype=np.float32, min_series=4)
    fleet.check(engine, t0)
    d0 = _declines(te.WIDE_NONFINITE)

    def nan(vals, ts):
        vals[6, 1] = np.nan

    fleet.write(t0, 4, nan)
    got, want = fleet.query(engine, t0 + STEP), fleet.query(None, t0 + STEP)
    assert engine.last_roll_decline == te.WIDE_NONFINITE
    assert _declines(te.WIDE_NONFINITE) == d0 + 1
    assert set(got) == set(want) and got
    for k in got:
        np.testing.assert_array_equal(got[k], want[k])


def test_a_dedup_rewind_on_a_wide_tile_declines(tmp_path):
    """Under a dedup interval an advance whose covered end lies inside an
    interval rewinds to its boundary; the host does not know the last raw
    value of the rows it shortens, so an increments tile declines with its
    reason counted, and the cold rebuild (on the device) answers as the
    host does."""
    f = _Fleet(tmp_path / "s")
    f.store.close()
    f.store = Storage(str(tmp_path / "d"), dedup_interval_ms=SCRAPE)
    try:
        t0 = f.t0
        f.write(t0 - 160 * SCRAPE, 160)
        f.store.force_flush()
        engine = te.TPUEngine(value_dtype=np.float32, min_series=4)
        end = t0 + 7_000          # inside an interval: the next advance cuts
        f.check(engine, end)
        d0, w0 = _declines(te.WIDE_REWIND), _wide("device")
        f.write(t0, 4)
        f.check(engine, end + STEP)
        assert engine.last_roll_decline == te.WIDE_REWIND
        assert _declines(te.WIDE_REWIND) == d0 + 1
        assert _wide("device") == w0 + ROWS
    finally:
        f.store.close()
