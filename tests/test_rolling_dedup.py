"""The resident rolling window under a dedup interval: a refresh rewinds
the tile to the last dedup window boundary B before the oldest new sample
(and the covered end), drops the samples later than B, appends the
storage's deduplicated refetch of (B, end] and recomputes the [G, T]
columns later than B. Over several ticks of an HA pair's writes (two
replicas of every counter, replica B offset into the interval) the rolling
answer must equal the cold rebuild (VM_DEVICE_RESIDENT=0) at rtol 1e-12,
in every case that places a new sample where only the rewind gets it
right."""

import numpy as np
import pytest

from victoriametrics_tpu.ops import device_rollup
from victoriametrics_tpu.query import tpu_engine as te
from victoriametrics_tpu.query.exec import exec_query
from victoriametrics_tpu.query.types import EvalConfig
from victoriametrics_tpu.storage.storage import Storage
from victoriametrics_tpu.utils import fasttime
from victoriametrics_tpu.utils import metrics as metricslib

STEP = 60_000
SCRAPE = 15_000
NS = 64
DUR = 20 * STEP
TICKS = 4
T0 = (fasttime.unix_ms() - 3 * 3_600_000) // STEP * STEP
# no `rate`: its previous-sample gate reads the scrape-interval estimate of
# the row's newest samples, which a column reused from an earlier refresh
# keeps (RingBlock's contract, dedup or not); these cases' extra samples
# move that estimate, so only estimate-free rollups compare at 1e-12
QUERIES = ["sum by (g)(increase(hx[5m]))", "max by (g)(max_over_time(hx[2m]))"]


def _value(i, ts):
    """A counter read at `ts`: increasing in time (by at least 1 a
    second) at a rate that wanders, so which sample of a window survives
    shows in every answer."""
    t = (np.asarray(ts, np.float64) - T0) / 1000.0
    return (1.0 + i % 5) * t + 3.0 * (t + np.sin(0.05 * t) / 0.05) + 5e4


class _Pair:
    """Replica A scrapes series i at t_from + 15 s x j + jitter, j = 1..k;
    replica B at t_from + 15 s x j + offset_i + jitter, j = 0..k-1: so
    B's first scrape of a tick often lands at or before the covered end
    (benchmark/deployments/counters_ha.py's shape)."""

    def __init__(self, store, replicas=2, seed=7):
        self.s = store
        self.replicas = replicas
        self.rng = np.random.default_rng(seed)
        self.off = self.rng.integers(0, SCRAPE, NS)
        self.newest = {}  # series -> (ts, value) of the newest sample

    def write(self, t_from, k, extra=None):
        rows = []
        for i in range(NS):
            j = np.arange(k, dtype=np.int64)
            ts = [t_from + SCRAPE * (j + 1)]
            if self.replicas == 2:
                ts.append(t_from + SCRAPE * j + self.off[i])
            ts = np.concatenate(ts) + self.rng.integers(-2000, 2001,
                                                        len(ts) * k)
            pts = list(zip(ts.tolist(), _value(i, ts).tolist()))
            if extra is not None:
                pts += extra(i, self.newest.get(i))
            lab = {"__name__": "hx", "i": str(i), "g": f"g{i % 4}"}
            rows += [(lab, int(t), float(v)) for t, v in pts]
            newest = max(pts, key=lambda p: (p[0], p[1]))
            if i not in self.newest or newest[0] >= self.newest[i][0]:
                self.newest[i] = newest
        self.s.add_rows(rows)


def _as_map(rows):
    return {r.metric_name.marshal(): np.asarray(r.values) for r in rows}


def _query(store, engine, q, end, **kw):
    return _as_map(exec_query(EvalConfig(start=end - DUR, end=end,
                                         step=STEP, storage=store,
                                         tpu=engine, **kw), q))


def _check(got, want, msg):
    assert set(got) == set(want) and got, msg
    for k in got:
        np.testing.assert_array_equal(np.isnan(got[k]), np.isnan(want[k]),
                                      err_msg=msg)
        np.testing.assert_allclose(got[k], want[k], rtol=1e-12,
                                   equal_nan=True, err_msg=msg)


def _cold(monkeypatch, store, q, end):
    with monkeypatch.context() as m:
        m.setenv("VM_DEVICE_RESIDENT", "0")
        return _query(store, te.TPUEngine(min_series=4), q, end,
                      disable_cache=True)


def _rolling_tiles(engine):
    wc = engine._wcache
    return [v for v in (wc._entries.values() if wc is not None else ())
            if isinstance(v, te.RollingTile)]


def _counter(name):
    return metricslib.REGISTRY.counter(name).get()


def _declines(reason):
    return _counter(metricslib.format_name(
        "vm_device_roll_declines_total", {"reason": reason}))


# each tick adds, per series, samples where only a rewind reads them right
# (hi: the covered end, the previous query's end; newest: the series'
# newest sample so far)
def _tie(i, hi, newest):
    # an equal timestamp: the storage keeps the larger value
    return [(newest[0], newest[1] + 1e-4)]


def _late(i, hi, newest):
    # later than the rewind boundary, at or before the covered end: spread
    # over the whole dedup window that ends there
    t = hi - 1_000 - 211 * i
    return [(t, float(_value(i, t)))]


def _boundary(i, hi, newest):
    # exactly on window boundaries: the covered end and one interval on
    t = np.array([hi, hi + SCRAPE])
    return list(zip(t.tolist(), _value(i, t).tolist()))


def _same_window(i, hi, newest):
    # the query ends 7 s past a boundary (the case's phase): one just before
    # the next end, one just after it in the same dedup window, so the
    # next refresh must replace the first with the second
    t = np.array([hi + STEP - 2_000, hi + STEP + 3_000])
    return list(zip(t.tolist(), _value(i, t).tolist()))


# name -> (dedup interval, query end's phase past the step, extra, merge)
CASES = {
    "replica_b_late": (SCRAPE, 0, None, False),
    "tie_larger_value_wins": (SCRAPE, 0, _tie, False),
    "late_inside_covered": (SCRAPE, 0, _late, False),
    "on_boundary": (SCRAPE, 0, _boundary, False),
    "same_window_as_newest": (SCRAPE, 7_000, _same_window, False),
    "interval_not_dividing_step": (25_000, 0, None, False),
    "merge_between_ticks": (SCRAPE, 0, _tie, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_rolling_under_dedup_matches_cold(tmp_path, monkeypatch, case):
    interval, phase, extra, merge = CASES[case]
    store = Storage(str(tmp_path / "s"), dedup_interval_ms=interval)
    try:
        pair = _Pair(store)
        pair.write(T0 - 40 * 60_000, 160)
        store.force_flush()
        engine = te.TPUEngine(min_series=4)
        end = T0 + phase
        for q in QUERIES:
            _check(_query(store, engine, q, end), _cold(monkeypatch, store,
                                                        q, end), q)
        rw0 = _counter("vm_device_roll_rewinds_total")
        for tick in range(TICKS):
            pair.write(end, 4, extra and (
                lambda i, newest, hi=end: extra(i, hi, newest)))
            if merge:
                store.force_flush()
                store.force_merge()
            end += STEP
            for q in QUERIES:
                _check(_query(store, engine, q, end),
                       _cold(monkeypatch, store, q, end),
                       f"{case} tick {tick}: {q}")
        rts = _rolling_tiles(engine)
        assert len(rts) == 1 and rts[0].appends == TICKS, \
            engine.last_roll_decline
        assert _counter("vm_device_roll_rewinds_total") > rw0
    finally:
        store.close()


def test_late_data_beyond_rewind_declines(tmp_path, monkeypatch):
    """A sample older than one step plus one interval before the covered
    end cannot be rewound to: the advance declines with its reason
    counted, and the cold rebuild answers."""
    store = Storage(str(tmp_path / "s"), dedup_interval_ms=SCRAPE)
    try:
        pair = _Pair(store)
        pair.write(T0 - 40 * 60_000, 160)
        store.force_flush()
        engine = te.TPUEngine(min_series=4)
        q = QUERIES[0]
        _query(store, engine, q, T0)
        d0 = _declines(te.REWIND_TOO_DEEP)
        deep = T0 - STEP - SCRAPE - 30_000
        pair.write(T0, 4, lambda i, newest: [(deep, float(_value(i, deep)))])
        end = T0 + STEP
        _check(_query(store, engine, q, end),
               _cold(monkeypatch, store, q, end), "deep late data")
        assert engine.last_roll_decline == te.REWIND_TOO_DEEP
        assert _declines(te.REWIND_TOO_DEEP) == d0 + 1
    finally:
        store.close()


def test_mesh_under_dedup_declines(tmp_path, monkeypatch):
    """The rewind has no series-sharded form: a mesh engine makes no
    resident window under a dedup interval, counts the decline each
    time, and still answers as the cold rebuild does."""
    import jax

    from victoriametrics_tpu.parallel.mesh import make_mesh
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    store = Storage(str(tmp_path / "s"), dedup_interval_ms=SCRAPE)
    try:
        pair = _Pair(store)
        pair.write(T0 - 40 * 60_000, 160)
        store.force_flush()
        engine = te.TPUEngine(min_series=4, mesh=make_mesh(devs[:8]))
        q = QUERIES[0]
        d0 = _declines(te.DEDUP_ON_MESH)
        end = T0
        for tick in range(2):
            _check(_query(store, engine, q, end),
                   _cold(monkeypatch, store, q, end), f"mesh tick {tick}")
            pair.write(end, 4)
            end += STEP
        assert _declines(te.DEDUP_ON_MESH) == d0 + 2
        assert not _rolling_tiles(engine)
    finally:
        store.close()


def test_no_dedup_appends_without_rewind(tmp_path, monkeypatch):
    """With no dedup interval the advance is the plain append: one
    append_tile launch a tick, no rewind program, no rewind counted."""
    calls = {"append": 0, "rewind": 0}

    def spy(name, fn):
        def call(*a):
            calls[name] += 1
            return fn(*a)
        return call

    monkeypatch.setattr(device_rollup, "append_tile",
                        spy("append", device_rollup.append_tile))
    monkeypatch.setattr(device_rollup, "rewind_append_tile",
                        spy("rewind", device_rollup.rewind_append_tile))
    store = Storage(str(tmp_path / "s"))
    try:
        pair = _Pair(store, replicas=1)
        pair.write(T0 - 40 * 60_000, 160)
        store.force_flush()
        engine = te.TPUEngine(min_series=4)
        q = QUERIES[0]
        end = T0
        _query(store, engine, q, end)
        rw0 = _counter("vm_device_roll_rewinds_total")
        for tick in range(2):
            # strictly newer than the covered end: A's first scrape is at
            # least 13 s past it
            pair.write(end, 4)
            end += STEP
            _check(_query(store, engine, q, end),
                   _cold(monkeypatch, store, q, end), f"tick {tick}")
        assert calls == {"append": 2, "rewind": 0}
        assert _rolling_tiles(engine)[0].cuts == []
        assert _counter("vm_device_roll_rewinds_total") == rw0
    finally:
        store.close()
