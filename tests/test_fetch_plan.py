"""The series plan of the fetch (storage/storage.py _SeriesPlan): what a
fetch derives from its series set alone is kept per (the index's tsid
list, structural version), and a fetch that finds its plan gives the
answer a fetch that has to build it gives, field for field.  The oracle
is a hit against a miss (the memo cleared), never a second code path;
every case also runs with the native library switched off for the fetch
(the split path and the per-header fall-back)."""

import numpy as np
import pytest

from victoriametrics_tpu import native
from victoriametrics_tpu.storage.storage import Storage
from victoriametrics_tpu.storage.tag_filters import TagFilter
from victoriametrics_tpu.utils import metrics as metricslib

pytestmark = pytest.mark.requires_native  # columnar ingest resolves keys there

# a minute grid well inside one UTC day and one month
T0 = 1_700_000_000_000 // 86_400_000 * 86_400_000 + 6 * 3_600_000
STEP = 60_000
S = 300
FILTERS = [TagFilter(b"", b"fp")]
FIELDS = ("metric_ids", "ts", "vals", "counts", "raw_names", "metric_names",
          "stale_rows", "ds_res")


def _counts() -> dict:
    return {r: metricslib.REGISTRY.counter(
        f'vm_fetch_plan_total{{result="{r}"}}').get()
        for r in ("hit", "miss")}


def _moved(c0: dict) -> dict:
    return {r: v - c0[r] for r, v in _counts().items()}


def _scrape(st, t: int, ids, value: float = 1.0, name: str = "fp") -> None:
    body = "\n".join(f'{name}{{idx="{i:04d}",job="j{i % 7}"}} {value + i} {t}'
                     for i in ids)
    cr = native.parse_prom_columnar(body.encode(), t)
    assert cr is not None
    assert st.add_rows_columnar(cr) == len(ids)


def _fields(cols) -> dict:
    out = {}
    for f in FIELDS:
        v = getattr(cols, f)
        if f == "metric_names" and v is not None:
            v = [mn.marshal() for mn in v]
        out[f] = v
    return out


def _assert_same(a, b) -> None:
    fa, fb = _fields(a), _fields(b)
    for f in FIELDS:
        x, y = fa[f], fb[f]
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            assert x is not None and y is not None, f
            assert x.dtype == y.dtype and x.shape == y.shape, f
            assert x.tobytes() == y.tobytes(), f
        else:
            assert x == y, f


@pytest.fixture(params=["native", "no_native"])
def store(request, tmp_path, monkeypatch):
    st = Storage(str(tmp_path / "st"))

    def fetch(lo, hi, **kw):
        # the library stays in for ingest; the fetch alone loses it
        with monkeypatch.context() as m:
            if request.param == "no_native":
                m.setattr(native, "available", lambda: False)
            return st.search_columns(FILTERS, lo, hi, **kw)
    st.fetch = fetch
    yield st
    st.close()


# -- a hit equals a miss ------------------------------------------------------

def _every_series(st):
    for k in range(3):
        _scrape(st, T0 + k * 15_000, range(S), k)
    return T0, T0 + STEP, S


def _a_subset(st):
    _scrape(st, T0 - 10 * STEP, range(S))
    for k in range(3):
        _scrape(st, T0 + k * 15_000, range(0, S, 3), k)
    return T0, T0 + STEP, len(range(0, S, 3))


def _mem_and_file_parts(st):
    _scrape(st, T0, range(S))
    st.force_flush()                      # a file part
    _scrape(st, T0 + 15_000, range(0, S, 2))
    st.table.flush_pending()              # an in-memory part
    _scrape(st, T0 + 30_000, range(S))
    st.table.flush_pending()              # a second one
    _scrape(st, T0 + 45_000, range(5, S))  # and pending rows
    return T0, T0 + STEP, S


def _two_partitions(st):
    import datetime
    d = datetime.datetime.fromtimestamp(T0 / 1e3, datetime.timezone.utc)
    nxt = (d.replace(day=1) + datetime.timedelta(days=32)).replace(
        day=1, hour=0, minute=0, second=0, microsecond=0)
    edge = int(nxt.timestamp() * 1000)
    for k in range(-2, 2):
        _scrape(st, edge + k * 15_000, range(S), k)
    assert len(st.table.partitions_for_range(edge - STEP, edge + STEP)) == 2
    return edge - STEP, edge + STEP, S


SCENES = [_every_series, _a_subset, _mem_and_file_parts, _two_partitions]


def _hit_and_miss(st, lo, hi, **kw):
    """(the answer on a plan hit, the answer on a miss), the counter
    checked on the way."""
    st._plan_memo.clear()
    c0 = _counts()
    st.fetch(lo, hi, **kw)
    assert _moved(c0) == {"hit": 0, "miss": 1}
    hit = st.fetch(lo, hi, **kw)
    assert _moved(c0) == {"hit": 1, "miss": 1}
    st._plan_memo.clear()
    miss = st.fetch(lo, hi, **kw)
    assert _moved(c0) == {"hit": 1, "miss": 2}
    return hit, miss


@pytest.mark.parametrize("scene", SCENES, ids=lambda f: f.__name__[1:])
def test_a_hit_answers_as_a_miss_does(store, scene):
    lo, hi, n = scene(store)
    hit, miss = _hit_and_miss(store, lo, hi)
    _assert_same(hit, miss)
    assert hit.n_series == n == len(hit.raw_names) == len(hit.metric_names)
    assert hit.raw_names == sorted(hit.raw_names)
    # against the per-block reference of the same storage
    ref = {sd.raw_name: (sd.timestamps, sd.values)
           for sd in store._search_series_blocks(FILTERS, lo, hi)}
    assert set(ref) == set(hit.raw_names)
    for r, raw in enumerate(hit.raw_names):
        k = int(hit.counts[r])
        np.testing.assert_array_equal(hit.ts[r, :k], ref[raw][0])
        np.testing.assert_array_equal(hit.vals[r, :k], ref[raw][1])


def test_a_series_the_index_cannot_name_is_dropped(store, monkeypatch):
    lo, hi, n = _every_series(store)
    full = store.fetch(lo, hi)
    lost = int(full.metric_ids[7])
    real = store.idb.get_metric_name_raw_by_id
    monkeypatch.setattr(store.idb, "get_metric_name_raw_by_id",
                        lambda mid: None if mid == lost else real(mid))
    hit, miss = _hit_and_miss(store, lo, hi)
    _assert_same(hit, miss)
    assert hit.n_series == n - 1 and lost not in hit.metric_ids
    assert hit.raw_names == full.raw_names[:7] + full.raw_names[8:]
    assert hit.ts.tobytes() == np.delete(full.ts, 7, axis=0).tobytes()
    assert hit.vals.tobytes() == np.delete(full.vals, 7, axis=0).tobytes()


@pytest.mark.parametrize("limit,raises", [(99, True), (100, False)])
def test_the_limit_counts_the_series_with_samples(store, limit, raises):
    """max_series is held against the series that HAVE blocks in range
    (100 of the plan's 300), on a hit as on a miss."""
    lo, hi, n = _a_subset(store)
    assert n == 100
    if not raises:
        hit, miss = _hit_and_miss(store, lo, hi, max_series=limit)
        _assert_same(hit, miss)
        assert hit.n_series == n
        (plan,) = store._plan_memo.values()
        assert plan.mids_sorted.size == S
        return
    store._plan_memo.clear()
    for _ in range(2):  # the miss, then the hit
        with pytest.raises(ResourceWarning, match="matches 100 series"):
            store.fetch(lo, hi, max_series=limit)


def test_a_callers_own_list_is_a_miss_every_time(store):
    lo, hi, n = _every_series(store)
    want = store.fetch(lo, hi)
    tsids = store.idb.search_tsids(FILTERS, lo, hi)
    c0 = _counts()
    for k in range(2):
        got = store.fetch(lo, hi, _tsids=tsids[10:60])
        assert _moved(c0) == {"hit": 0, "miss": k + 1}
        assert got.n_series == 50
        rows = [want.raw_names.index(r) for r in got.raw_names]
        assert got.ts.tobytes() == want.ts[rows].tobytes()
        assert got.vals.tobytes() == want.vals[rows].tobytes()
        np.testing.assert_array_equal(got.metric_ids, want.metric_ids[rows])


# -- what changes the series set changes the plan -----------------------------

def _refresh(st, tick: int, ids):
    """One tick of a rolling panel: a scrape, then the tail fetch."""
    t = T0 + tick * STEP
    _scrape(st, t + 15_000, ids, tick)
    return st.fetch(t + 1, t + STEP)


def test_a_rolling_loop_builds_its_plan_once(store):
    n = 5
    c0 = _counts()
    for tick in range(n + 1):
        cols = _refresh(store, tick, range(S))
        assert cols.n_series == S
        assert _moved(c0) == {"hit": tick, "miss": 1}


def test_a_new_series_is_in_the_next_answer(store):
    _refresh(store, 0, range(S))
    c0 = _counts()
    assert _refresh(store, 1, range(S)).n_series == S
    assert _moved(c0) == {"hit": 1, "miss": 0}
    cols = _refresh(store, 2, range(S + 1))   # idx 0300 is new
    assert _moved(c0) == {"hit": 1, "miss": 1}
    assert cols.n_series == S + 1
    assert sum(b'0300' in r for r in cols.raw_names) == 1
    assert _refresh(store, 3, range(S + 1)).n_series == S + 1
    assert _moved(c0) == {"hit": 2, "miss": 1}


def test_a_deleted_series_is_gone_from_the_next_answer(store):
    _refresh(store, 0, range(S))
    assert _refresh(store, 1, range(S)).n_series == S
    sv = store.structural_version
    assert store.delete_series(FILTERS + [TagFilter(b"idx", b"0042")]) == 1
    assert store.structural_version > sv
    c0 = _counts()
    gone = _refresh(store, 2, range(S))
    assert _moved(c0) == {"hit": 0, "miss": 1}
    assert gone.n_series == S - 1
    assert not any(b'0042' in r for r in gone.raw_names)
    assert _refresh(store, 3, range(S)).n_series == S - 1
    assert _moved(c0) == {"hit": 1, "miss": 1}


def test_the_answers_name_lists_are_its_own(store):
    lo, hi, _ = _every_series(store)
    first = store.fetch(lo, hi)
    want = (list(first.raw_names), [mn.marshal() for mn in first.metric_names])
    first.raw_names.reverse()
    first.metric_names.clear()
    c0 = _counts()
    second = store.fetch(lo, hi)
    assert _moved(c0) == {"hit": 1, "miss": 0}
    assert (second.raw_names,
            [mn.marshal() for mn in second.metric_names]) == want
    assert second.raw_names is not first.raw_names
    (plan,) = store._plan_memo.values()
    assert second.raw_names is not plan.raws
    assert second.metric_names is not plan.names
