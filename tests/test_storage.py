"""Storage engine tests — coverage modeled on the reference's
lib/storage/storage_test.go, index_db_test.go, partition behaviors:
roundtrips through flush/merge/restart, tag-filter search semantics,
deletes, snapshots, dedup, retention."""

import os

import numpy as np
import pytest

from victoriametrics_tpu.storage.block import Block, rows_to_blocks
from victoriametrics_tpu.storage.index_db import IndexDB
from victoriametrics_tpu.storage.mergeset import Table as MsTable
from victoriametrics_tpu.storage.metric_name import MetricName
from victoriametrics_tpu.storage.storage import Storage
from victoriametrics_tpu.storage.tag_filters import TagFilter, filters_from_dict
from victoriametrics_tpu.storage.tsid import TSID, generate_tsid

T0 = 1_753_700_000_000


class TestMetricName:
    def test_marshal_roundtrip(self):
        mn = MetricName.from_dict(
            {"__name__": "http_requests", "job": "api", "instance": "h1:9090"})
        out = MetricName.unmarshal(mn.marshal())
        assert out == mn
        assert out.to_dict()["job"] == "api"

    def test_label_sorting_canonical(self):
        a = MetricName.from_labels([("b", "2"), ("a", "1"), ("__name__", "m")])
        b = MetricName.from_labels([("a", "1"), ("__name__", "m"), ("b", "2")])
        assert a.marshal() == b.marshal()

    def test_escaping_weird_bytes(self):
        mn = MetricName.from_labels(
            [("__name__", b"m\x00etric"), (b"k\x01ey", b"v\x02al\x00ue")])
        out = MetricName.unmarshal(mn.marshal())
        assert out == mn

    def test_empty_value_dropped(self):
        mn = MetricName.from_dict({"__name__": "m", "empty": ""})
        assert mn.labels == []


class TestMergeset:
    def test_add_search_flush_reopen(self, tmp_path):
        p = str(tmp_path / "ms")
        t = MsTable(p)
        items = [f"key{i:05d}".encode() for i in range(1000)]
        t.add_items(items)
        assert list(t.search_prefix(b"key0001")) == \
            [f"key0001{j}".encode() for j in range(10)]
        t.flush_to_disk()
        t.close()
        t2 = MsTable(p)
        assert list(t2.search_prefix(b"key00999")) == [b"key00999"]
        assert t2.has_item(b"key00000")
        assert not t2.has_item(b"nope")
        t2.close()

    def test_dedup_across_parts(self, tmp_path):
        t = MsTable(str(tmp_path / "ms"))
        t.add_items([b"x", b"y"])
        t.flush_to_disk()
        t.add_items([b"x", b"z"])
        assert list(t.iter_from(b"")) == [b"x", b"y", b"z"]
        t.close()

    def test_large_flush_triggers_file_parts(self, tmp_path):
        t = MsTable(str(tmp_path / "ms"))
        for batch in range(5):
            t.add_items([os.urandom(24) for _ in range(40_000)])
        t.flush_to_disk()
        n = sum(1 for _ in t.iter_from(b""))
        assert n == 200_000
        t.close()


class TestBlocks:
    def test_block_roundtrip(self):
        tsid = TSID(1, 2, 3, 4)
        ts = np.arange(100, dtype=np.int64) * 15000 + T0
        vals = np.round(np.random.default_rng(0).uniform(0, 100, 100), 2)
        blk = Block.from_floats(tsid, ts, vals)
        h, td, vd = blk.marshal()
        out = Block.unmarshal(h, td, vd)
        np.testing.assert_array_equal(out.timestamps, ts)
        np.testing.assert_allclose(out.float_values(), vals, rtol=1e-12)
        assert out.tsid == tsid

    def test_rows_split_at_8k(self):
        tsid = TSID(1, 2, 3, 4)
        n = 20_000
        ts = np.arange(n, dtype=np.int64) * 1000 + T0
        vals = np.ones(n)
        blocks = list(rows_to_blocks(tsid, ts, vals))
        assert [b.rows for b in blocks] == [8192, 8192, 3616]


def mk_storage(tmp_path, **kw):
    # T0 is a literal 2025-07-28: state a retention that does not depend
    # on today's date (the 13-month default dropped it at merge from
    # 2026-09-04 on)
    kw.setdefault("retention_ms", 100 * 365 * 86_400_000)
    return Storage(str(tmp_path / "s"), **kw)


def write_sample_data(s, n_series=20, n_samples=50):
    rows = []
    for i in range(n_series):
        mn = {"__name__": "cpu_usage" if i % 2 == 0 else "mem_usage",
              "instance": f"host{i % 5}", "core": str(i)}
        for j in range(n_samples):
            rows.append((mn, T0 + j * 15000, float(i * 1000 + j)))
    s.add_rows(rows)
    return n_series * n_samples


class TestStorage:
    def test_write_search_roundtrip(self, tmp_path):
        s = mk_storage(tmp_path)
        write_sample_data(s)
        res = s.search_series(filters_from_dict({"__name__": "cpu_usage"}),
                              T0, T0 + 10_000_000)
        assert len(res) == 10
        one = [r for r in res if r.metric_name.get_label(b"core") == b"0"][0]
        assert one.timestamps.size == 50
        np.testing.assert_allclose(one.values, np.arange(50.0))
        s.close()

    def test_filters(self, tmp_path):
        s = mk_storage(tmp_path)
        write_sample_data(s)
        f = filters_from_dict({"__name__": "cpu_usage", "instance": "host0"})
        res = s.search_series(f, T0, T0 + 10_000_000)
        assert len(res) == 2  # cores 0 and 10
        # negative filter
        f = filters_from_dict({"__name__": "cpu_usage",
                               "instance": ("!=", "host0")})
        assert len(s.search_series(f, T0, T0 + 10_000_000)) == 8
        # regex
        f = filters_from_dict({"__name__": ("=~", "cpu_.*")})
        assert len(s.search_series(f, T0, T0 + 10_000_000)) == 10
        # regex alternation uses or-values
        f = filters_from_dict({"__name__": ("=~", "cpu_usage|mem_usage")})
        assert len(s.search_series(f, T0, T0 + 10_000_000)) == 20
        s.close()

    def test_persistence_across_reopen(self, tmp_path):
        s = mk_storage(tmp_path)
        write_sample_data(s)
        s.close()
        s2 = mk_storage(tmp_path)
        res = s2.search_series(filters_from_dict({"__name__": "cpu_usage"}),
                               T0, T0 + 10_000_000)
        assert len(res) == 10
        assert res[0].timestamps.size == 50
        s2.close()

    def test_flush_and_merge_preserve_data(self, tmp_path):
        s = mk_storage(tmp_path)
        write_sample_data(s)
        s.force_flush()
        write_sample_data(s)  # duplicates!
        s.force_merge()
        res = s.search_series(filters_from_dict({"__name__": "cpu_usage"}),
                              T0, T0 + 10_000_000)
        # duplicate timestamps collapse at query time
        assert len(res) == 10
        assert res[0].timestamps.size == 50
        s.close()

    def test_label_apis(self, tmp_path):
        s = mk_storage(tmp_path)
        write_sample_data(s)
        assert s.label_names() == ["__name__", "core", "instance"]
        assert s.label_values("instance") == [f"host{i}" for i in range(5)]
        assert s.label_values("__name__") == ["cpu_usage", "mem_usage"]
        assert s.series_count() == 20
        s.close()

    def test_delete_series(self, tmp_path):
        s = mk_storage(tmp_path)
        write_sample_data(s)
        n = s.delete_series(filters_from_dict({"__name__": "mem_usage"}))
        assert n == 10
        assert s.search_series(filters_from_dict({"__name__": "mem_usage"}),
                               T0, T0 + 10_000_000) == []
        # survives merge and reopen
        s.force_merge()
        s.close()
        s2 = mk_storage(tmp_path)
        assert s2.search_series(filters_from_dict({"__name__": "mem_usage"}),
                                T0, T0 + 10_000_000) == []
        assert len(s2.search_series(filters_from_dict({"__name__": "cpu_usage"}),
                                    T0, T0 + 10_000_000)) == 10
        s2.close()

    def test_snapshot_restore(self, tmp_path):
        s = mk_storage(tmp_path)
        write_sample_data(s)
        name = s.create_snapshot()
        assert name in s.list_snapshots()
        snap = os.path.join(s.snapshots_dir(), name)
        s.close()
        # "restore": open a storage rooted at the snapshot layout
        dst = tmp_path / "restored"
        os.makedirs(dst)
        os.rename(os.path.join(snap, "data"), dst / "data")
        os.rename(os.path.join(snap, "indexdb"), dst / "indexdb")
        os.rename(os.path.join(snap, "format.json"), dst / "format.json")
        s2 = Storage(str(dst))
        res = s2.search_series(filters_from_dict({"__name__": "cpu_usage"}),
                               T0, T0 + 10_000_000)
        assert len(res) == 10
        s2.close()

    def test_dedup_interval(self, tmp_path):
        s = mk_storage(tmp_path, dedup_interval_ms=60_000)
        rows = [({"__name__": "m"}, T0 + i * 15_000, float(i))
                for i in range(40)]
        s.add_rows(rows)
        res = s.search_series(filters_from_dict({"__name__": "m"}),
                              T0, T0 + 10_000_000)
        # 40 samples @15s -> one survivor per occupied 60s bucket
        want = len({(T0 + i * 15_000) // 60_000 for i in range(40)})
        assert res[0].timestamps.size == want
        # each survivor is the last sample of its bucket
        assert res[0].values[0] == 2.0
        s.close()

    def test_stale_nan_roundtrip(self, tmp_path):
        from victoriametrics_tpu.ops import decimal as dec
        s = mk_storage(tmp_path)
        s.add_rows([({"__name__": "m"}, T0, 5.0),
                    ({"__name__": "m"}, T0 + 1000, dec.STALE_NAN)])
        s.force_flush()
        res = s.search_series(filters_from_dict({"__name__": "m"}),
                              T0, T0 + 10_000)
        assert dec.is_stale_nan(res[0].values[1:2]).all()
        s.close()

    def test_multi_month_partitions(self, tmp_path):
        s = mk_storage(tmp_path)
        month = 31 * 86_400_000
        s.add_rows([({"__name__": "m"}, T0, 1.0),
                    ({"__name__": "m"}, T0 + month, 2.0),
                    ({"__name__": "m"}, T0 + 2 * month, 3.0)])
        s.force_flush()
        assert len(s.table.partition_names) == 3
        res = s.search_series(filters_from_dict({"__name__": "m"}),
                              T0, T0 + 3 * month)
        assert res[0].values.tolist() == [1.0, 2.0, 3.0]
        # partial range hits only overlapping partitions
        res = s.search_series(filters_from_dict({"__name__": "m"}),
                              T0 + month, T0 + month)
        assert res[0].values.tolist() == [2.0]
        s.close()

    def test_retention_drops_old_partitions(self, tmp_path):
        s = mk_storage(tmp_path, retention_ms=40 * 86_400_000)
        import time as _t
        now = int(_t.time() * 1e3)
        s.add_rows([({"__name__": "m"}, now - 100 * 86_400_000, 1.0),
                    ({"__name__": "m"}, now, 2.0)])
        s.force_flush()
        assert len(s.table.partition_names) >= 2
        dropped = s.enforce_retention()
        assert dropped >= 1
        res = s.search_series(filters_from_dict({"__name__": "m"}),
                              now - 200 * 86_400_000, now)
        assert res[0].values.tolist() == [2.0]
        s.close()

    def test_flock_exclusive(self, tmp_path):
        s = mk_storage(tmp_path)
        with pytest.raises(RuntimeError, match="locked"):
            Storage(str(tmp_path / "s"))
        s.close()

    def test_tsdb_status(self, tmp_path):
        s = mk_storage(tmp_path)
        write_sample_data(s)
        st = s.tsdb_status()
        assert st["totalSeries"] == 20
        top = {e["name"]: e["count"] for e in st["seriesCountByMetricName"]}
        assert top == {"cpu_usage": 10, "mem_usage": 10}
        s.close()

    def test_register_metric_names(self, tmp_path):
        s = mk_storage(tmp_path)
        s.register_metric_names([{"__name__": "registered", "a": "b"}])
        assert s.series_count() == 1
        assert s.label_values("__name__") == ["registered"]
        s.close()


class TestConcurrency:
    def test_concurrent_read_write_with_merges(self, tmp_path):
        """Regression: thread-unsafe shared zstd ctx segfaulted; merges
        closing parts under readers corrupted reads."""
        import threading
        s = mk_storage(tmp_path)
        errs = []

        def writer(tid):
            try:
                for j in range(15):
                    s.add_rows([({"__name__": "conc", "i": str(k),
                                  "t": str(tid)}, T0 + j * 1000, float(j))
                                for k in range(40)])
                    if j % 5 == 0:
                        s.force_flush()
            except Exception as e:
                errs.append(e)

        def reader():
            try:
                for _ in range(25):
                    s.search_series(filters_from_dict({"__name__": "conc"}),
                                    T0, T0 + 100_000)
            except Exception as e:
                errs.append(e)

        ths = ([threading.Thread(target=writer, args=(i,)) for i in range(2)]
               + [threading.Thread(target=reader) for _ in range(2)])
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert errs == []
        res = s.search_series(filters_from_dict({"__name__": "conc"}),
                              T0, T0 + 100_000)
        assert len(res) == 80
        s.close()


class TestReviewRegressions:
    def test_metric_id_with_zero_bytes_in_tag_scan(self, tmp_path):
        # metric ids whose BE encoding contains 0x00 must parse in value scans
        from victoriametrics_tpu.storage.index_db import IndexDB
        from victoriametrics_tpu.storage.tsid import TSID
        idb = IndexDB(str(tmp_path / "idb"))
        mn = MetricName.from_dict({"__name__": "m", "k": "v"})
        tsid = TSID(1, 2, 3, 256)  # BE bytes contain 0x00 and end 0x01 0x00
        idb.create_indexes_for_metric(mn, tsid)
        vals = list(idb._iter_tag_values(b"k"))
        assert vals == [(b"v", 256)]
        assert idb.label_values("k") == ["v"]
        idb.close()

    def test_regex_group_with_suffix_not_misexpanded(self, tmp_path):
        tf = TagFilter(b"x", b"(a|b)c", regex=True)
        assert tf.or_values is None  # falls back to real regex
        assert tf.match_value(b"ac") and tf.match_value(b"bc")
        assert not tf.match_value(b"a|bc")

    def test_label_apis_time_scoped(self, tmp_path):
        s = mk_storage(tmp_path)
        day = 86_400_000
        s.add_rows([({"__name__": "old", "gen": "0"}, T0 - 30 * day, 1.0),
                    ({"__name__": "new", "gen": "1"}, T0, 2.0)])
        s.force_flush()
        assert s.label_values("__name__", T0 - 3600_000, T0) == ["new"]
        assert set(s.label_values("__name__")) == {"new", "old"}
        assert "gen" in s.label_names(T0 - 3600_000, T0)
        s.close()

    def test_listed_unopenable_part_quarantined_and_restorable(
            self, tmp_path):
        """A listed part that fails to open is QUARANTINED (moved aside,
        bytes preserved, results flagged partial) — never rmtree'd and
        never silently dropped; the operator can restore it by moving it
        back and re-listing it in parts.json."""
        s = mk_storage(tmp_path)
        write_sample_data(s, n_series=2, n_samples=3)
        s.force_flush()
        s.close()
        import glob, json
        parts = glob.glob(str(tmp_path / "s" / "data" / "*" / "p_*"))
        assert parts
        victim = parts[0]
        pdir = os.path.dirname(victim)
        name = os.path.basename(victim)
        meta = os.path.join(victim, "metadata.json")
        orig = open(meta).read()
        open(meta, "w").write("{broken")
        s2 = mk_storage(tmp_path)
        # moved to quarantine/, bytes intact, served loudly partial
        qpath = os.path.join(pdir, "quarantine", name)
        assert not os.path.isdir(victim)
        assert os.path.isdir(qpath)
        assert s2.last_partial is True
        rep = s2.quarantine_report()
        assert len(rep) == 1 and rep[0]["part"] == name
        s2.close()
        # operator restore: heal metadata, move back, re-list
        open(os.path.join(qpath, "metadata.json"), "w").write(orig)
        os.rename(qpath, victim)
        os.rmdir(os.path.join(pdir, "quarantine"))
        manifest = os.path.join(pdir, "parts.json")
        listed = json.load(open(manifest))["parts"]
        json.dump({"parts": sorted(set(listed) | {name})},
                  open(manifest, "w"))
        s3 = mk_storage(tmp_path)
        assert s3.last_partial is False
        assert len(s3.search_series(filters_from_dict({"__name__": "cpu_usage"}),
                                    T0, T0 + 10_000_000)) == 1
        s3.close()


class TestDedupSemantics:
    """reference lib/storage/dedup.go:30-121 — right-inclusive windows,
    max-value tie-break preferring non-stale (issues 3333, 10196)."""

    def test_exact_multiple_closes_window(self):
        import numpy as np
        from victoriametrics_tpu.storage.dedup import deduplicate
        # a sample at an exact interval multiple belongs to the window
        # ENDING there, not the next one
        ts = np.array([60_000, 120_000, 120_001], dtype=np.int64)
        vals = np.array([1.0, 2.0, 3.0])
        kt, kv = deduplicate(ts, vals, 60_000)
        assert list(kt) == [60_000, 120_000, 120_001]
        # two samples inside (60000, 120000]
        ts = np.array([60_001, 120_000, 180_000], dtype=np.int64)
        vals = np.array([1.0, 2.0, 3.0])
        kt, kv = deduplicate(ts, vals, 60_000)
        assert list(kt) == [120_000, 180_000]
        assert list(kv) == [2.0, 3.0]

    def test_equal_ts_prefers_non_stale(self):
        import numpy as np
        from victoriametrics_tpu.ops import decimal as dec
        from victoriametrics_tpu.storage.dedup import deduplicate
        ts = np.array([100, 100, 100], dtype=np.int64)
        vals = np.array([5.0, 7.0, dec.STALE_NAN])
        kt, kv = deduplicate(ts, vals, 60_000)
        assert kt.size == 1 and kv[0] == 7.0
        # all stale -> stale marker survives
        vals = np.array([dec.STALE_NAN, dec.STALE_NAN], dtype=np.float64)
        kt, kv = deduplicate(ts[:2], vals, 60_000)
        assert dec.is_stale_nan(kv[:1]).all()

    def test_equal_ts_int64_mantissas(self):
        import numpy as np
        from victoriametrics_tpu.ops import decimal as dec
        from victoriametrics_tpu.storage.dedup import deduplicate
        ts = np.array([100, 100], dtype=np.int64)
        vals = np.array([42, dec.V_STALE_NAN], dtype=np.int64)
        kt, kv = deduplicate(ts, vals, 60_000)
        assert kv[0] == 42


class TestQueryPathCaches:
    def test_single_sample_blocks_not_collapsed_by_cache(self, tmp_path):
        # zero-length const payloads share file offsets; the block cache
        # must not return one series' block for another (regression)
        s = mk_storage(tmp_path)
        s.add_rows([({"__name__": "bm", "i": str(i)}, T0 + i * 1000, float(i))
                    for i in range(50)])
        s.force_flush()
        f = filters_from_dict({"__name__": "bm"})
        assert len(s.search_series(f, T0, T0 + 100_000)) == 50
        # second (warm, cache-served) query must see all series too
        assert len(s.search_series(f, T0, T0 + 100_000)) == 50
        s.close()

    def test_posting_cache_hits_and_invalidation(self, tmp_path):
        s = mk_storage(tmp_path)
        s.add_rows([({"__name__": "pc", "i": str(i)}, T0, float(i))
                    for i in range(10)])
        f = filters_from_dict({"__name__": "pc"})
        r1 = s.idb.search_metric_ids(f, T0, T0 + 1000)
        h0 = s.idb.filter_cache_hits
        r2 = s.idb.search_metric_ids(f, T0, T0 + 1000)
        assert s.idb.filter_cache_hits == h0 + 1
        assert (r1 == r2).all()
        # a new series invalidates the cached posting set
        s.add_rows([({"__name__": "pc", "i": "new"}, T0, 1.0)])
        r3 = s.idb.search_metric_ids(f, T0, T0 + 1000)
        assert r3.size == 11
        s.close()


class TestIngestFastPath:
    def test_day_rollover_creates_per_day_indexes(self, tmp_path):
        s = mk_storage(tmp_path)
        day_ms = 86_400_000
        base = (T0 // day_ms) * day_ms
        s.add_rows([({"__name__": "fr", "i": "1"}, base + 1000, 1.0)])
        # same series next day through the fast path
        s.add_rows([({"__name__": "fr", "i": "1"}, base + day_ms + 1000, 2.0)])
        f = filters_from_dict({"__name__": "fr"})
        # per-day index must find it on day 2 alone
        res = s.search_series(f, base + day_ms, base + day_ms + 10_000)
        assert len(res) == 1 and res[0].values[0] == 2.0
        s.close()

    def test_label_order_variants_resolve_same_tsid(self, tmp_path):
        s = mk_storage(tmp_path)
        s.add_rows([([(b"a", b"1"), (b"b", b"2"), (b"", b"lo")], T0, 1.0)])
        s.add_rows([([(b"b", b"2"), (b"a", b"1"), (b"", b"lo")],
                     T0 + 1000, 2.0)])
        res = s.search_series(filters_from_dict({"__name__": "lo"}),
                              T0, T0 + 10_000)
        assert len(res) == 1 and res[0].timestamps.size == 2
        s.close()

    def test_delete_purges_raw_cache(self, tmp_path):
        s = mk_storage(tmp_path)
        s.add_rows([({"__name__": "dp", "i": "1"}, T0, 1.0)])
        f = filters_from_dict({"__name__": "dp"})
        assert s.delete_series(f) == 1
        assert not s._tsid_cache_raw  # tombstoned ids must not linger
        assert len(s.search_series(f, T0, T0 + 10_000)) == 0
        s.close()


class TestInfluxEscapes:
    def test_escaped_tag_and_field_keys(self):
        from victoriametrics_tpu.ingest.parsers import parse_influx
        rows = list(parse_influx(
            'weird\\ m,ta\\,g=va\\=lue fo\\=o=3,value=3.5 123000000'))
        d = {tuple(sorted(r.labels)): (r.timestamp, r.value) for r in rows}
        names = {dict(r.labels)["__name__"] for r in rows}
        assert names == {"weird m_fo=o", "weird m"}
        for r in rows:
            assert dict(r.labels)["ta,g"] == "va=lue"
            assert r.timestamp == 123

    def test_tag_value_with_equals_same_on_both_paths(self):
        from victoriametrics_tpu.ingest.parsers import parse_influx
        fast = list(parse_influx('m,tag=a=b f=1 123000000'))
        # a quote elsewhere forces the slow path for the same tag
        slow = list(parse_influx('m,tag=a=b f=1,s="x" 123000000'))
        assert dict(fast[0].labels)["tag"] == "a=b"
        assert dict(slow[0].labels)["tag"] == "a=b"


class TestRollupBatchNonFinite:
    def test_inf_falls_back(self):
        import numpy as np
        from victoriametrics_tpu.ops import rollup_np
        from victoriametrics_tpu.ops.rollup_np import RollupConfig
        cfg = RollupConfig(start=T0, end=T0 + 120_000, step=60_000,
                           window=120_000)
        series = [(np.array([T0 - 10_000, T0 - 5_000], dtype=np.int64),
                   np.array([np.inf, 2.0]))]
        assert rollup_np.rollup_batch("sum_over_time", series, cfg) is None


class TestMultitenancy:
    """accountID:projectID isolation (lib/auth.Token, search.go:376)."""

    def test_identical_names_fully_isolated(self, tmp_path):
        s = mk_storage(tmp_path)
        t1, t2 = (1, 0), (1, 7)
        s.add_rows([({"__name__": "m", "i": "x"}, T0, 1.0)], tenant=t1)
        s.add_rows([({"__name__": "m", "i": "x"}, T0, 2.0)], tenant=t2)
        s.add_rows([({"__name__": "only1", "i": "y"}, T0, 3.0)], tenant=t1)
        f = filters_from_dict({"__name__": "m"})
        r1 = s.search_series(f, T0 - 1000, T0 + 1000, tenant=t1)
        r2 = s.search_series(f, T0 - 1000, T0 + 1000, tenant=t2)
        r0 = s.search_series(f, T0 - 1000, T0 + 1000)  # default tenant
        assert len(r1) == 1 and r1[0].values[0] == 1.0
        assert len(r2) == 1 and r2[0].values[0] == 2.0
        assert r0 == []
        # label APIs are tenant-scoped
        assert s.label_values("__name__", tenant=t1) == ["m", "only1"]
        assert s.label_values("__name__", tenant=t2) == ["m"]
        assert s.series_count(tenant=t1) == 2
        assert s.series_count(tenant=t2) == 1
        assert s.tenants() == [(1, 0), (1, 7)]
        # delete in one tenant leaves the other intact
        assert s.delete_series(f, tenant=t1) == 1
        assert s.search_series(f, T0 - 1000, T0 + 1000, tenant=t1) == []
        assert len(s.search_series(f, T0 - 1000, T0 + 1000, tenant=t2)) == 1
        s.close()

    def test_tenant_survives_restart(self, tmp_path):
        s = mk_storage(tmp_path)
        s.add_rows([({"__name__": "rt"}, T0, 5.0)], tenant=(9, 9))
        s.close()
        s2 = mk_storage(tmp_path)
        f = filters_from_dict({"__name__": "rt"})
        assert len(s2.search_series(f, T0 - 1000, T0 + 1000,
                                    tenant=(9, 9))) == 1
        assert s2.search_series(f, T0 - 1000, T0 + 1000) == []
        assert (9, 9) in s2.tenants()
        s2.close()


class TestFormatVersionGate:
    def test_old_layout_rejected_clearly(self, tmp_path):
        import json as _json
        root = tmp_path / "s"
        os.makedirs(root / "data")
        with pytest.raises(RuntimeError, match="on-disk format"):
            Storage(str(root))
        # wrong version in the marker also rejected
        import shutil as _sh
        _sh.rmtree(root)
        os.makedirs(root / "data")
        with open(root / "format.json", "w") as f:
            _json.dump({"format_version": 1}, f)
        with pytest.raises(RuntimeError, match="v1"):
            Storage(str(root))


class TestCardinalityLimiters:
    """lib/bloomfilter/limiter.go semantics (storage.go:2136)."""

    def test_hourly_limit_drops_over_budget(self, tmp_path):
        s = Storage(str(tmp_path / "cl"), max_hourly_series=10)
        rows = [({"__name__": "cl", "i": str(i)}, T0, float(i))
                for i in range(25)]
        s.add_rows(rows)
        m = s.metrics()
        assert m["vm_hourly_series_limit_max_series"] == 10
        assert m["vm_hourly_series_limit_current_series"] == 10
        # The bloom filter admits a rare false positive WITHOUT counting it
        # (limiter.go:62 semantics; metric ids are nanotime-seeded so the
        # probe positions differ run to run): every row is either dropped or
        # created a series, and at most a couple of FPs sneak past budget.
        dropped = m["vm_hourly_series_limit_rows_dropped_total"]
        created = s.series_count()
        assert dropped + created == 25
        assert 10 <= created <= 12
        # over-budget series created NO index entries (storage.go:2136
        # ordering: limiter gates index creation, not just data rows)
        assert s.new_series_created == created
        # tracked series keep flowing through the fast path
        n = s.add_rows([({"__name__": "cl", "i": "1"}, T0 + 15_000, 9.0)])
        assert n == 1
        assert s.metrics()["vm_hourly_series_limit_rows_dropped_total"] == \
            dropped
        s.close()

    def test_limiter_rotates(self):
        import time as _t
        from victoriametrics_tpu.storage.cardinality import BloomLimiter
        lim = BloomLimiter(2, rotation_s=3600)
        assert lim.add(1) and lim.add(2) and not lim.add(3)
        lim._bucket -= 1  # simulate the hour rolling over
        assert lim.add(3)  # budget reset
        assert lim.current_series == 1


class TestCachePersistence:
    def test_no_reresolve_storm_after_restart(self, tmp_path):
        s = Storage(str(tmp_path / "cp"))
        rows = [({"__name__": "cp", "i": str(i)}, T0, float(i))
                for i in range(200)]
        s.add_rows(rows)
        s.close()
        s2 = Storage(str(tmp_path / "cp"))
        before = s2.slow_row_inserts
        s2.add_rows([({"__name__": "cp", "i": str(i)}, T0 + 15_000, 1.0)
                     for i in range(200)])
        # every tsid came from the persisted cache: one cache-dict hit per
        # series, zero index lookups for day-known series
        assert s2.slow_row_inserts - before == 0
        assert s2.new_series_created == 0
        f = filters_from_dict({"__name__": "cp"})
        assert len(s2.search_series(f, T0, T0 + 100_000)) == 200
        s2.close()


class TestPerMonthIndex:
    def test_retention_drops_month_index_with_partition(self, tmp_path):
        now_ms = int(__import__("time").time() * 1000)
        old_ms = now_ms - 200 * 86_400_000
        s = Storage(str(tmp_path / "pm"), retention_ms=100 * 86_400_000)
        s.add_rows([({"__name__": "old", "i": "1"}, old_ms, 1.0)])
        s.add_rows([({"__name__": "new", "i": "1"}, now_ms, 2.0)])
        s.force_flush()
        months = os.path.join(str(tmp_path / "pm"), "indexdb", "months")
        assert len(os.listdir(months)) == 2
        dropped = s.enforce_retention()
        assert dropped >= 2  # data partition + month index
        live = os.listdir(months)
        assert len(live) == 1
        # new data still searchable through its per-day index
        f = filters_from_dict({"__name__": "new"})
        assert len(s.search_series(f, now_ms - 1000, now_ms + 1000)) == 1
        f = filters_from_dict({"__name__": "old"})
        assert s.search_series(f, old_ms - 1000, old_ms + 1000) == []
        s.close()
