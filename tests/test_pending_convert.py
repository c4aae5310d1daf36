"""Fresh rows become a readable part in one native pass (ISSUE 31):
`partition._chunks_to_inmemory_part` (native/pending.cpp, ordered by the
id space's TSID rank) against `_lexsort_to_inmemory_part`, the seven-key
lexsort it replaces where the input allows and its oracle.  Equal means
every array of `InmemoryPart._cols` element for element (mids, counts,
exponents, block min / max, starts, timestamps, mantissas) and the TSID
object every block resolves to.
"""

import time

import numpy as np
import pytest

from tests.apptest_helpers import Client
from victoriametrics_tpu import native
from victoriametrics_tpu.ops.decimal import STALE_NAN
from victoriametrics_tpu.storage import partition as plib
from victoriametrics_tpu.storage.block import MAX_ROWS_PER_BLOCK
from victoriametrics_tpu.storage.partition import PendingChunk
from victoriametrics_tpu.storage.storage import _ColumnarSpace
from victoriametrics_tpu.storage.tsid import TSID
from victoriametrics_tpu.utils import metrics as metricslib

pytestmark = pytest.mark.requires_native

BASE = 1_700_000_000_000
NATIVE = 'vm_pending_convert_rows_total{path="native"}'
LEXSORT = 'vm_pending_convert_rows_total{path="lexsort"}'
PHASE = 'vm_fetch_phase_seconds_total{phase="pending_convert"}'


def _metrics() -> dict:
    out = {}
    for line in metricslib.REGISTRY.write_prometheus().splitlines():
        if line and not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


def _tsid(rng, mid: int) -> TSID:
    """A few distinct values a key column, so the order is decided at
    every depth of the key."""
    return TSID(metric_group_id=int(rng.integers(1, 4)) << 40,
                job_id=int(rng.integers(0, 3)),
                instance_id=int(rng.integers(0, 5)), metric_id=mid,
                account_id=int(rng.integers(0, 2)),
                project_id=int(rng.integers(0, 2)))


def _space(rng, n_ids: int, mids=None) -> _ColumnarSpace:
    sp = _ColumnarSpace()
    mids = rng.permutation(n_ids) + 1000 if mids is None else mids
    sp.append_ids([_tsid(rng, int(m)) for m in mids], [0] * n_ids)
    return sp


def _tsid_order(sp) -> np.ndarray:
    n = len(sp.tsids)
    return np.lexsort((sp.mid[:n], sp.inst[:n], sp.job[:n], sp.grp[:n],
                       sp.proj[:n], sp.acc[:n]))


def _chunk(sp, ids, ts, vals) -> PendingChunk:
    return PendingChunk(sp, np.asarray(ids, np.int64),
                        np.asarray(ts, np.int64),
                        np.asarray(vals, np.float64))


def _scrapes(rng, sp, ids, k: int) -> PendingChunk:
    """k scrapes of every id of `ids`, scrape-major as an import posts
    them: whole-number counters, +-2 s of jitter."""
    ids = np.asarray(ids, np.int64)
    ts = (BASE + np.arange(k)[:, None] * 15_000
          + rng.integers(-2000, 2000, (k, ids.size)))
    vals = np.cumsum(rng.integers(0, 50, (k, ids.size)), axis=0)
    return _chunk(sp, np.tile(ids, k), ts.ravel(), vals.ravel())


def _assert_same_part(got, want):
    assert got.rows == want.rows
    assert (got.min_ts, got.max_ts) == (want.min_ts, want.max_ts)
    if want.rows == 0:
        assert got.block_list == want.block_list == []
        return
    names = ("mids", "counts", "exponents", "block_min", "block_max",
             "starts", "timestamps", "mantissas")
    for name, a, b in zip(names, got.columns(), want.columns()):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a, b), name
    starts = want.columns()[5]
    pick = np.unique(np.linspace(0, starts.size - 1, 64).astype(np.int64))
    for k in pick:
        assert got._lazy[2](int(starts[k])) is want._lazy[2](int(starts[k]))
    assert np.array_equal(got._lazy[1], want._lazy[1])  # block ends


def _both(items):
    """(native part, oracle part) of one batch; the native one has to
    have taken the batch."""
    m0 = _metrics()
    got = plib._rows_to_inmemory_part(list(items))
    m1 = _metrics()
    n = sum(len(x) for x in items)
    assert m1[NATIVE] - m0[NATIVE] == n and m1[LEXSORT] == m0[LEXSORT]
    return got, plib._lexsort_to_inmemory_part(list(items), 64)


# -- the equality cases -------------------------------------------------------

def _case_id_order(rng, how: str, n_chunks: int):
    sp = _space(rng, 300)
    order = _tsid_order(sp)
    ids = {"tsid_order": order, "reversed": order[::-1],
           "shuffled": rng.permutation(300)}[how]
    return [_scrapes(rng, sp, ids, 4) for _ in range(n_chunks)]


def _case_duplicates(rng):
    """Rows of one (series, timestamp) with different values, inside a
    chunk and across chunks: ingest order decides which one dedup keeps."""
    sp = _space(rng, 40)
    ids = rng.integers(0, 40, 600)
    ts = BASE + rng.integers(0, 6, 600) * 1000
    return [_chunk(sp, ids[:350], ts[:350], rng.random(350)),
            _chunk(sp, ids[350:], ts[350:], rng.random(250))]


def _case_out_of_order(rng):
    sp = _space(rng, 64)
    ids = rng.integers(0, 64, 5000)
    return [_chunk(sp, ids, BASE + rng.integers(0, 10**6, 5000),
                   rng.normal(size=5000).round(3))]


def _case_long_unsorted_run(rng):
    """One series' run past the insertion sort's length, unsorted."""
    sp = _space(rng, 8)
    ids = np.concatenate([np.full(700, 3), rng.integers(0, 8, 200)])
    return [_chunk(sp, ids, BASE + rng.integers(0, 900, 900) * 500,
                   rng.integers(0, 100, 900))]


def _case_equal_keys(rng):
    """One series under two raw keys: two ids, equal key columns, one
    TSID order between them; their rows interleave by timestamp."""
    sp = _space(rng, 30)
    twin = sp.tsids[7]
    sp.append_ids([TSID(twin.metric_group_id, twin.job_id,
                        twin.instance_id, twin.metric_id, twin.account_id,
                        twin.project_id)], [0])
    ids = np.concatenate([rng.integers(0, 31, 400), np.full(40, 7),
                          np.full(40, 30)])
    ids = rng.permutation(ids)
    return [_chunk(sp, ids, BASE + rng.integers(0, 50, ids.size) * 1000,
                   rng.random(ids.size))]


def _case_big_series(rng):
    sp = _space(rng, 5)
    n = 2 * MAX_ROWS_PER_BLOCK + 77
    ids = np.concatenate([np.full(n, 2), rng.integers(0, 5, 1000)])
    ts = BASE + np.concatenate([np.arange(n) * 10,
                                rng.integers(0, 10**6, 1000)])
    return [_chunk(sp, ids, ts, np.arange(ids.size, dtype=np.float64))]


def _case_special_values(rng):
    sp = _space(rng, 50)
    special = np.array([np.nan, STALE_NAN, np.inf, -np.inf, 1e300, -1e300,
                        0.0, -0.0, 12345678.0, 1e15, 0.1, 2.5e-300])
    vals = special[rng.integers(0, special.size, 3000)]
    plain = rng.random(3000) < 0.5
    vals[plain] = rng.integers(0, 10**9, int(plain.sum()))
    return [_chunk(sp, rng.integers(0, 50, 3000),
                   BASE + rng.integers(0, 10**5, 3000), vals)]


def _case_empty_chunk(rng):
    sp = _space(rng, 20)
    return [_scrapes(rng, sp, np.arange(20), 3), _chunk(sp, [], [], []),
            _scrapes(rng, sp, np.arange(20)[::-1], 2)]


def _case_small_batch_of_a_large_space(rng):
    """Far fewer rows than ranks: the pass sorts the rows by (rank, ts)
    and makes no table of every rank."""
    sp = _space(rng, 5000)
    first = _scrapes(rng, sp, rng.permutation(5000), 1)  # pays for the rank
    plib._rows_to_inmemory_part([first])
    ids = rng.integers(0, 5000, 200)
    return [_chunk(sp, ids, BASE + rng.integers(0, 50, 200) * 1000,
                   rng.random(200))]


CASES = {
    "one_chunk_tsid_order": lambda r: _case_id_order(r, "tsid_order", 1),
    "one_chunk_reversed": lambda r: _case_id_order(r, "reversed", 1),
    "one_chunk_shuffled": lambda r: _case_id_order(r, "shuffled", 1),
    "five_chunks_tsid_order": lambda r: _case_id_order(r, "tsid_order", 5),
    "five_chunks_reversed": lambda r: _case_id_order(r, "reversed", 5),
    "five_chunks_shuffled": lambda r: _case_id_order(r, "shuffled", 5),
    "duplicate_series_ts_rows": _case_duplicates,
    "out_of_order_timestamps": _case_out_of_order,
    "long_unsorted_run": _case_long_unsorted_run,
    "two_ids_equal_keys": _case_equal_keys,
    "series_over_a_block": _case_big_series,
    "special_values": _case_special_values,
    "empty_chunk": _case_empty_chunk,
    "small_batch_of_a_large_space": _case_small_batch_of_a_large_space,
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_native_part_equals_the_lexsort_part(case, seed):
    rng = np.random.default_rng(1000 * seed + len(case))
    got, want = _both(CASES[case](rng))
    _assert_same_part(got, want)


def test_only_empty_chunks_make_an_empty_part():
    sp = _space(np.random.default_rng(5), 4)
    got, want = _both([_chunk(sp, [], [], [])])
    _assert_same_part(got, want)


def test_duplicates_keep_ingest_order():
    """Spelled out once, beside the oracle: of two rows of one series
    with one timestamp the later ingested one comes later."""
    sp = _space(np.random.default_rng(9), 3)
    got, _ = _both([_chunk(sp, [1, 1, 0], [BASE, BASE, BASE], [1., 2., 9.]),
                    _chunk(sp, [1], [BASE], [3.])])
    blk = [b for b in got.block_list
           if b.tsid is sp.tsids[1]][0]
    assert blk.values.tolist() == [1, 2, 3] and blk.scale == 0


# -- the rank follows the space -----------------------------------------------

def test_a_space_that_grows_rebuilds_its_rank():
    rng = np.random.default_rng(11)
    sp = _space(rng, 100)
    _assert_same_part(*_both([_scrapes(rng, sp, rng.permutation(100), 3)]))
    rank_before = sp.tsid_rank(0)[0]
    assert rank_before.size == 100
    # 60 more series, sorting among the first hundred
    sp.append_ids([_tsid(rng, 5000 + i) for i in range(60)], [0] * 60)
    _assert_same_part(*_both([_scrapes(rng, sp, rng.permutation(160), 3)]))
    rank_after = sp.tsid_rank(0)[0]
    assert rank_after.size == 160 and rank_after is not rank_before
    assert sp.tsid_rank(0)[0] is rank_after  # read once, kept


def test_set_tsid_readmission_rebuilds_the_rank():
    rng = np.random.default_rng(12)
    sp = _ColumnarSpace()
    tsids = [_tsid(rng, 1000 + i) for i in range(50)]
    late = tsids[20]
    tsids[20] = None  # over the cardinality budget when first seen
    sp.append_ids(tsids, [3 if t is None else 0 for t in tsids])
    live = np.array([i for i in range(50) if i != 20])
    _assert_same_part(*_both([_scrapes(rng, sp, live, 4)]))
    rank_before = sp.tsid_rank(0)[0]
    sp.set_tsid(20, late)
    got, want = _both([_scrapes(rng, sp, rng.permutation(50), 4)])
    _assert_same_part(got, want)
    assert sp.tsid_rank(0)[0] is not rank_before
    assert any(b.tsid is late for b in got.block_list)


def test_a_changed_space_reads_its_rank_only_once_it_pays():
    """A small batch of a large space that has just changed is sorted as
    before; the rank is read once the rows sorted without it outnumber
    the ids it would have to sort."""
    rng = np.random.default_rng(13)
    sp = _space(rng, 1000)
    m0 = _metrics()
    for k in range(3):  # 3 x 300 rows < 1000 ids
        ch = _scrapes(rng, sp, rng.integers(0, 1000, 300), 1)
        part = plib._rows_to_inmemory_part([ch])
        _assert_same_part(part, plib._lexsort_to_inmemory_part([ch], 64))
    m1 = _metrics()
    assert m1[LEXSORT] - m0[LEXSORT] == 900 and m1[NATIVE] == m0[NATIVE]
    ch = _scrapes(rng, sp, rng.integers(0, 1000, 300), 1)
    _assert_same_part(*_both([ch]))  # 1200 rows >= 1000 ids: native now


def test_conversions_beside_a_registering_writer():
    """More converting threads than cores beside one writer that keeps
    registering series (and so voiding the rank): every part equals the
    oracle's, whichever rank it was ordered by."""
    import sys
    import threading
    rng = np.random.default_rng(14)
    sp = _space(rng, 200)
    parked, lock, stop = [], threading.Lock(), threading.Event()
    bad = []

    def writer():
        w = np.random.default_rng(15)
        while not stop.is_set():
            n = len(sp.tsids)
            with sp.lock:
                sp.append_ids([_tsid(w, 10**6 + n + i) for i in range(20)],
                              [0] * 20)
            ch = _scrapes(w, sp, w.permutation(n + 20), 2)
            with lock:
                parked.append(ch)

    def reader():
        while not stop.is_set() or parked:
            with lock:
                ch = parked.pop() if parked else None
            if ch is None:
                time.sleep(0.001)
                continue
            try:
                _assert_same_part(plib._rows_to_inmemory_part([ch]),
                                  plib._lexsort_to_inmemory_part([ch], 64))
            except Exception as e:  # noqa: BLE001 — reported by the test
                bad.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=writer)] + \
        [threading.Thread(target=reader) for _ in range(12)]
    try:
        for t in threads:
            t.start()
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(30)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not bad, bad[:3]
    assert len(sp.tsids) > 200


# -- the fall-backs -----------------------------------------------------------

def _fallback_tuple(rng, monkeypatch):
    sp = _space(rng, 30)
    t = _tsid(rng, 77)
    return [_scrapes(rng, sp, np.arange(30), 3), (t, BASE + 5, 1.5),
            (t, BASE + 1, 2.5)]


def _fallback_two_spaces(rng, monkeypatch):
    a, b = _space(rng, 30), _space(rng, 30)
    return [_scrapes(rng, a, np.arange(30), 3),
            _scrapes(rng, b, np.arange(30), 3)]


def _fallback_no_library(rng, monkeypatch):
    sp = _space(rng, 30)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load", lambda: None)
    return [_scrapes(rng, sp, np.arange(30), 300)]


@pytest.mark.parametrize("make", [_fallback_tuple, _fallback_two_spaces,
                                  _fallback_no_library],
                         ids=lambda f: f.__name__[len("_fallback_"):])
def test_anything_else_is_sorted_as_before(make, monkeypatch):
    items = make(np.random.default_rng(21), monkeypatch)
    n = sum(1 if isinstance(x, tuple) else len(x) for x in items)
    m0 = _metrics()
    got = plib._rows_to_inmemory_part(list(items))
    m1 = _metrics()
    assert m1[LEXSORT] - m0[LEXSORT] == n and m1[NATIVE] == m0[NATIVE]
    want = plib._lexsort_to_inmemory_part(list(items), 64)
    assert got.rows == want.rows == n
    for a, b in zip(got.columns(), want.columns()):
        assert np.array_equal(a, b)


# -- served -------------------------------------------------------------------

def test_an_import_is_readable_at_once_through_the_native_pass(tmp_path):
    """Import over HTTP, ask query_range at once: the newest step holds
    the imported value, every imported row went through the native pass,
    and the serving thread's wait for it has its own stage."""
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.httpapi.server import HTTPServer
    from victoriametrics_tpu.storage.storage import Storage
    s = Storage(str(tmp_path / "s"))
    srv = HTTPServer("127.0.0.1", 0)
    PrometheusAPI(s).register(srv)
    srv.start()
    try:
        c = Client(srv.port)
        now = int(time.time()) // 60 * 60 - 3600
        n_series, k = 40, 5

        def post(t_end: int, bump: int) -> int:
            lines = [f'fresh_total{{i="{i}"}} {100 * i + j + bump} '
                     f'{(t_end - (k - 1 - j) * 15) * 1000}'
                     for j in range(k) for i in range(n_series)]
            code, body = c.post("/api/v1/import/prometheus",
                                ("\n".join(lines) + "\n").encode())
            assert code in (200, 204), body
            return len(lines)

        post(now - 60, 0)   # registers the series: whichever path
        c.query_range("fresh_total", now - 120, now - 60, 60)
        m0 = _metrics()
        rows = post(now, 1000)
        res = c.query_range("fresh_total", now - 120, now, 60)
        m1 = _metrics()
        assert res["status"] == "success"
        result = res["data"]["result"]
        assert len(result) == n_series
        for r in result:
            i = int(r["metric"]["i"])
            t, v = r["values"][-1]
            assert t == now and float(v) == 100 * i + k - 1 + 1000
        assert m1[NATIVE] - m0[NATIVE] == rows
        assert m1[LEXSORT] == m0[LEXSORT]
        assert m1[PHASE] > m0[PHASE]
    finally:
        srv.stop()
        s.close()
