"""Sharded ingestion pipeline (VM_INGEST_SHARDS): the acceptance
property — byte-identical data parts and identical data_version /
append-log observables between the striped parallel write path and the
sequential one — plus the two-generation cache rotation semantics, the
merge-concurrency gate, and the flusher-thread lifecycle.

Metric ids are time-seeded (MetricIDGenerator), so equality harnesses
pin the generator before ingesting; everything else is the production
code path.
"""

import hashlib
import os
import threading
import time

import numpy as np
import pytest

from victoriametrics_tpu.storage import partition as partition_mod
from victoriametrics_tpu.utils import metrics as metricslib
from victoriametrics_tpu.utils import workpool
from victoriametrics_tpu.utils.workingset import WorkingSetCache

try:
    from victoriametrics_tpu import native
    from victoriametrics_tpu.storage.storage import Storage
    from victoriametrics_tpu.storage.tag_filters import filters_from_dict
    _HAVE_STORAGE = True
except ImportError:  # optional deps (zstandard) missing
    _HAVE_STORAGE = False

needs_storage = pytest.mark.skipif(not _HAVE_STORAGE,
                                   reason="storage deps unavailable")
# canonical native gate (conftest skips the marked tests when the codec
# library is unavailable)
needs_native = pytest.mark.requires_native

T0 = 1_753_700_000_000  # 2025-07-28
DAY = 86_400_000
# T0 is a literal 2025-07-28: a stated retention, so that no merge drops
# it whatever today's date is
RETENTION_MS = 100 * 365 * 86_400_000


def _hash_tree(root) -> dict:
    """relpath -> sha256 for every file under root."""
    out = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for fn in sorted(filenames):
            full = os.path.join(dirpath, fn)
            with open(full, "rb") as f:
                out[os.path.relpath(full, root)] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


def _observables(s) -> tuple:
    return (s.data_version, list(s._append_log), s.rows_added,
            s.new_series_created)


def _mk_store(path, shards, monkeypatch, **kw) -> "Storage":
    monkeypatch.setenv("VM_INGEST_SHARDS", str(shards))
    monkeypatch.setenv("VM_SEARCH_WORKERS", "4" if shards > 1 else "1")
    kw.setdefault("retention_ms", RETENTION_MS)
    s = Storage(str(path), **kw)
    s._mid_gen._next = 1_000_000  # deterministic ids across runs
    return s


def _legacy_rows():
    """dict labels + raw byte keys + a malformed key + day rollovers."""
    rows = []
    for i in range(40):
        rows.append(({"__name__": "leg", "i": str(i)},
                     T0 + i * 1000, float(i)))
    rows.append((b"bad{{{", T0, 9.0))            # malformed: dropped
    for i in range(20):
        rows.append((b'raw{i="%d"}' % i, T0 + i * 1000, float(i)))
    for i in range(40):                          # day rollover, fast path
        rows.append(({"__name__": "leg", "i": str(i)},
                     T0 + DAY + i * 1000, float(i + 1)))
    return rows


def _columnar_batches():
    keys = [f'cm{{i="{i}"}}'.encode() for i in range(32)]
    keybuf = b"".join(keys)
    klens = np.fromiter((len(k) for k in keys), np.int64, len(keys))
    koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
    out = []
    for step in range(3):
        k = 60
        ts = (T0 + (step * k + np.arange(k, dtype=np.int64))[None, :]
              * 15_000)
        ts = np.broadcast_to(ts, (len(keys), k)).reshape(-1).copy()
        if step == 2:
            ts = ts + DAY  # rollover batch
        vals = (ts % 10**9).astype(np.float64)
        out.append((keybuf, np.repeat(koffs, k), np.repeat(klens, k),
                    ts, vals))
    return out


# -- parallel vs sequential byte equality ------------------------------------

@needs_storage
class TestShardedEquality:
    def _finish(self, s):
        s.force_flush()
        obs = _observables(s)
        data = os.path.join(s.path, "data")
        s.close()
        return _hash_tree(data), obs

    def test_legacy_rows_byte_identical(self, tmp_path, monkeypatch):
        """add_rows with dict/bytes/malformed/day-rollover rows: the
        striped path's parts equal the sequential path's byte for byte
        (the async pending spill is forced via a tiny row cap)."""
        monkeypatch.setattr(partition_mod, "MAX_PENDING_ROWS", 64)
        results = []
        for shards, sub in ((1, "seq"), (4, "par")):
            s = _mk_store(tmp_path / sub, shards, monkeypatch)
            try:
                s.add_rows(_legacy_rows())
                s.add_rows(_legacy_rows())  # warm-cache second pass
            finally:
                results.append(self._finish(s))
        (h_seq, o_seq), (h_par, o_par) = results
        assert o_seq == o_par
        assert h_seq == h_par
        assert len(h_seq) > 0

    @needs_native
    def test_columnar_byte_identical(self, tmp_path, monkeypatch):
        monkeypatch.setattr(partition_mod, "MAX_PENDING_ROWS", 512)
        results = []
        for shards, sub in ((1, "seq"), (4, "par")):
            s = _mk_store(tmp_path / sub, shards, monkeypatch)
            try:
                for args in _columnar_batches():
                    s.add_rows_columnar(native.ColumnarRows(*args))
            finally:
                results.append(self._finish(s))
        (h_seq, o_seq), (h_par, o_par) = results
        assert o_seq == o_par
        assert h_seq == h_par

    def test_cardinality_limited_byte_identical(self, tmp_path, monkeypatch):
        """With a tight hourly budget the SAME series must win the
        admission race in both modes (limiter probes run in input order
        on the calling thread), so parts and drop counts stay equal."""
        results = []
        for shards, sub in ((1, "seq"), (4, "par")):
            s = _mk_store(tmp_path / sub, shards, monkeypatch,
                          max_hourly_series=12)
            try:
                s.add_rows(_legacy_rows())
                dropped = s.hourly_limiter.rows_dropped
            finally:
                h, o = self._finish(s)
                results.append((h, o, dropped))
        (h_seq, o_seq, d_seq), (h_par, o_par, d_par) = results
        assert o_seq == o_par
        assert d_seq == d_par > 0
        assert h_seq == h_par

    def test_multiwriter_merged_equality(self, tmp_path, monkeypatch):
        """Concurrent writers with pre-registered series: after
        force_merge the canonical merged part depends only on the row
        set, so the sharded store equals the sequential one."""
        def run(shards, sub):
            s = _mk_store(tmp_path / sub, shards, monkeypatch)
            try:
                # register every series first so metric ids don't depend
                # on which writer thread resolves first
                s.add_rows([({"__name__": "mw", "w": str(w), "i": str(i)},
                             T0 - 60_000 + w * 16 + i, 0.0)
                            for w in range(4) for i in range(16)])
                errs = []

                def writer(w):
                    try:
                        for j in range(1, 40):
                            s.add_rows([
                                ({"__name__": "mw", "w": str(w),
                                  "i": str(i)},
                                 T0 + j * 1000 + w, float(j))
                                for i in range(16)])
                    except BaseException as e:  # noqa: BLE001
                        errs.append(e)

                threads = [threading.Thread(target=writer, args=(w,),
                                            daemon=True) for w in range(4)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=60)
                assert not errs, errs
                s.force_flush()
                s.force_merge()
                rows = s.table.rows
            finally:
                data = os.path.join(s.path, "data")
                s.close()
            return _hash_tree(data), rows

        h_seq, r_seq = run(1, "seq")
        h_par, r_par = run(4, "par")
        assert r_seq == r_par == 4 * 16 + 4 * 39 * 16
        assert h_seq == h_par

    def test_spill_error_does_not_poison_partition(self, tmp_path,
                                                   monkeypatch):
        """A failing async pending conversion drops its batch with
        consistent bookkeeping (like a failed inline conversion) instead
        of wedging every later drain on the cached exception."""
        monkeypatch.setattr(partition_mod, "MAX_PENDING_ROWS", 32)
        s = _mk_store(tmp_path / "s", 4, monkeypatch)
        real = partition_mod._rows_to_inmemory_part
        calls = {"n": 0}

        def flaky(rows, *a, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("boom")
            return real(rows, *a, **kw)

        monkeypatch.setattr(partition_mod, "_rows_to_inmemory_part", flaky)
        err0 = metricslib.REGISTRY.counter(
            "vm_ingest_spill_errors_total").get()
        try:
            # 40 rows > cap: spilled to the pool, conversion fails; the
            # failure is logged+counted at the source, NOT re-raised into
            # unrelated readers/flushers
            s.add_rows([({"__name__": "pe", "i": str(i)}, T0 + i, float(i))
                        for i in range(40)])
            s.force_flush()
            # the partition is NOT poisoned: later ingest/flush/query work
            s.add_rows([({"__name__": "pe2", "i": str(i)}, T0 + i, 1.0)
                        for i in range(8)])
            s.force_flush()
            got = s.search_series(filters_from_dict({"__name__": "pe2"}),
                                  T0 - 10**6, T0 + 10**6)
            assert len(got) == 8
            assert s.table.rows == 8  # failed batch dropped, books balance
            assert metricslib.REGISTRY.counter(
                "vm_ingest_spill_errors_total").get() == err0 + 1
        finally:
            s.close()

    @needs_native
    def test_sharded_query_during_spill(self, tmp_path, monkeypatch):
        """Reads issued while async pending conversions are in flight
        see every ingested row exactly once."""
        monkeypatch.setattr(partition_mod, "MAX_PENDING_ROWS", 256)
        s = _mk_store(tmp_path / "s", 4, monkeypatch)
        try:
            total = 0
            for args in _columnar_batches():
                total += s.add_rows_columnar(native.ColumnarRows(*args))
                cols = s.search_columns(
                    filters_from_dict({"__name__": "cm"}),
                    T0 - 10**6, T0 + 10**10)
                assert cols.n_samples == total
            assert s.table.rows == total
        finally:
            s.close()


# -- generation-rotated caches ------------------------------------------------

class TestWorkingSetCache:
    def test_no_wipe_at_capacity(self):
        c = WorkingSetCache(4, "t")
        for i in range(4):
            c.put(i, i * 10)
        assert c.rotations == 0
        c.put(4, 40)  # overflow: rotates, does NOT wipe
        assert c.rotations == 1
        # every previously cached entry is still served (from prev gen)
        for i in range(5):
            assert c.get(i) == i * 10

    def test_promotion_keeps_working_set_alive(self):
        c = WorkingSetCache(2, "t")
        c.put("a", 1)
        c.put("b", 2)
        c.put("c", 3)          # rotation #1: cur={c}, prev={a,b}
        assert c.rotations == 1
        assert c.get("a") == 1  # promoted into cur
        c.put("d", 4)           # rotation #2: prev={a,c}... "a" survives
        assert c.get("a") == 1
        # an entry idle across two full generations is gone
        assert c.get("b") is None

    def test_len_bool_items_filter(self):
        c = WorkingSetCache(2, "t")
        assert not c
        c.put("a", 1)
        c.put("b", 2)
        c.put("c", 3)
        assert c and len(c) == 3          # distinct keys across both gens
        assert dict(c.items()) == {"a": 1, "b": 2, "c": 3}
        c.filter(lambda k, v: v != 2)
        assert c.get("b") is None and len(c) == 2
        c.clear()
        assert not c and len(c) == 0

    def test_put_overwrite_does_not_rotate(self):
        c = WorkingSetCache(2, "t")
        c.put("a", 1)
        c.put("b", 2)
        c.put("a", 9)  # overwrite of a resident key: no rotation
        assert c.rotations == 0
        assert c.get("a") == 9


@needs_storage
class TestIndexCacheRotation:
    def test_filter_cache_rotates_instead_of_wiping(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("VM_INGEST_SHARDS", "1")
        s = Storage(str(tmp_path / "s"))
        try:
            s.add_rows([({"__name__": f"fc{i}", "x": "1"}, T0, 1.0)
                        for i in range(6)])
            idb = s.idb
            idb.MAX_FILTER_CACHE = 2  # instance-level shrink
            f0 = filters_from_dict({"__name__": "fc0"})
            idb.search_metric_ids(f0, T0, T0 + 1000)
            # overflow the current generation with distinct selectors
            for i in range(1, 4):
                idb.search_metric_ids(
                    filters_from_dict({"__name__": f"fc{i}"}),
                    T0, T0 + 1000)
            # f0 rotated into the previous generation, NOT wiped: the
            # repeat is a cache hit
            h0 = idb.filter_cache_hits
            idb.search_metric_ids(f0, T0, T0 + 1000)
            assert idb.filter_cache_hits == h0 + 1
        finally:
            s.close()

    def test_filter_cache_counters_are_registry_backed(self, tmp_path,
                                                       monkeypatch):
        monkeypatch.setenv("VM_INGEST_SHARDS", "1")
        g0 = metricslib.REGISTRY.counter(
            'vm_cache_requests_total{type="indexdb/tagFilters"}').get()
        s = Storage(str(tmp_path / "s"))
        try:
            s.add_rows([({"__name__": "rc", "x": "1"}, T0, 1.0)])
            f = filters_from_dict({"__name__": "rc"})
            r0 = s.idb.filter_cache_requests
            s.idb.search_metric_ids(f, T0, T0 + 1000)
            s.idb.search_metric_ids(f, T0, T0 + 1000)
            assert s.idb.filter_cache_requests == r0 + 2
            assert s.idb.filter_cache_hits >= 1
            # the property shims are read-only views over Counters
            with pytest.raises(AttributeError):
                s.idb.filter_cache_requests = 0
            assert metricslib.REGISTRY.counter(
                'vm_cache_requests_total{type="indexdb/tagFilters"}'
            ).get() >= g0 + 2
        finally:
            s.close()

    def test_id_caches_survive_rotation(self, tmp_path, monkeypatch):
        monkeypatch.setenv("VM_INGEST_SHARDS", "1")
        s = Storage(str(tmp_path / "s"))
        try:
            s.add_rows([({"__name__": "idc", "i": str(i)}, T0, 1.0)
                        for i in range(8)])
            idb = s.idb
            idb._name_cache = WorkingSetCache(4, "test.name")
            mids = [int(m) for m in
                    idb.search_metric_ids(
                        filters_from_dict({"__name__": "idc"}),
                        T0, T0 + 1000)]
            for m in mids:        # fills past capacity: rotates, no wipe
                assert idb.get_metric_name_by_id(m) is not None
            assert idb._name_cache.rotations >= 1
            for m in mids:        # all still resolvable (cache or index)
                assert idb.get_metric_name_by_id(m) is not None
        finally:
            s.close()


# -- merge gate ---------------------------------------------------------------

class TestMergeGate:
    def test_admission_bounds_concurrency(self):
        gate = workpool.MergeGate(limit=1)
        order = []
        entered = threading.Event()
        release = threading.Event()

        def holder():
            with gate:
                order.append("A-in")
                entered.set()
                release.wait(10)
            order.append("A-out")

        def waiter():
            entered.wait(10)
            with gate:          # blocks until the holder releases
                order.append("B-in")

        a = threading.Thread(target=holder, daemon=True)
        b = threading.Thread(target=waiter, daemon=True)
        a.start()
        b.start()
        entered.wait(10)
        # B must be queued, not admitted
        deadline = time.monotonic() + 2
        while gate.pending == 0 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert gate.active == 1 and gate.pending == 1
        assert order == ["A-in"]
        release.set()
        a.join(timeout=10)
        b.join(timeout=10)
        assert order == ["A-in", "A-out", "B-in"]
        assert gate.active == 0 and gate.pending == 0

    def test_env_sizing_and_metrics_exposed(self, monkeypatch):
        monkeypatch.setenv("VM_MERGE_WORKERS", "3")
        assert workpool.MergeGate().limit == 3
        monkeypatch.setenv("VM_MERGE_WORKERS", "junk")
        assert workpool.MergeGate().limit == (os.cpu_count() or 1)
        text = metricslib.REGISTRY.write_prometheus()
        assert "vm_merge_pending" in text
        assert "vm_merge_active" in text


# -- service-thread lifecycle + ingest metrics --------------------------------

@needs_storage
class TestIngestRuntime:
    def test_flusher_thread_joined_on_close(self, tmp_path, monkeypatch):
        monkeypatch.setenv("VM_INGEST_SHARDS", "2")
        s = Storage(str(tmp_path / "s"))
        flusher = s._flusher
        assert flusher.is_alive()
        s.close()
        assert not flusher.is_alive()

    def test_ingest_metrics_move(self, tmp_path, monkeypatch):
        monkeypatch.setenv("VM_INGEST_SHARDS", "2")
        rows0 = metricslib.REGISTRY.counter("vm_ingest_rows_total").get()
        res0 = metricslib.ingest_phase("resolve").get()
        s = Storage(str(tmp_path / "s"))
        try:
            s.add_rows([({"__name__": "im", "i": str(i)}, T0, float(i))
                        for i in range(10)])
            s.force_flush()
        finally:
            s.close()
        assert metricslib.REGISTRY.counter(
            "vm_ingest_rows_total").get() == rows0 + 10
        assert metricslib.ingest_phase("resolve").get() > res0
        assert metricslib.ingest_phase("flush").get() > 0
        text = metricslib.REGISTRY.write_prometheus()
        assert 'vm_ingest_phase_seconds_total{phase="register"}' in text
        assert "vm_ingest_shard_lock_wait_seconds_total" in text

    def test_shards_env_escape_hatch(self, tmp_path, monkeypatch):
        monkeypatch.setenv("VM_INGEST_SHARDS", "1")
        s = Storage(str(tmp_path / "s"))
        try:
            assert len(s._shards) == 1
            assert not workpool.ingest_parallel_enabled()
        finally:
            s.close()
        monkeypatch.setenv("VM_INGEST_SHARDS", "5")
        s = Storage(str(tmp_path / "s2"))
        try:
            assert len(s._shards) == 5
        finally:
            s.close()
