"""Ring-buffer rollup result cache (O(new samples) steady-state serving):
in-place tail merges must be indistinguishable — bit for bit — from the
full-rebuild oracle (VM_RESULT_CACHE_RING=0) and from a cold nocache
evaluation, across rolling refreshes, series churn, the volatile-tail
clip and backfill resets; byte-bounded LRU eviction; and the
serve-priority merge gate."""

import hashlib
import os
import threading
import time

import numpy as np
import pytest

from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
from victoriametrics_tpu.query import rollup_result_cache as rrc
from victoriametrics_tpu.query.exec import exec_query
from victoriametrics_tpu.query.types import EvalConfig
from victoriametrics_tpu.storage.storage import Storage
from victoriametrics_tpu.utils import metrics as metricslib

STEP = 60_000
SCRAPE = 15_000
N0 = 400          # initial scrapes per series
NS = 12           # series
DUR = 40 * STEP   # query window


def _sha(rows) -> str:
    h = hashlib.sha256()
    for ts in sorted(rows, key=lambda t: t.metric_name.marshal()):
        h.update(ts.metric_name.marshal())
        h.update(np.ascontiguousarray(ts.values).tobytes())
    return h.hexdigest()


def _mk_store(tmp_path, name="s") -> tuple[Storage, int]:
    """Store with live-anchored counters (fresh scrapes land inside the
    OFFSET_MS volatile window, as in production)."""
    s = Storage(str(tmp_path / name))
    now = int(time.time() * 1000)
    t0 = (now - (N0 - 1) * SCRAPE) // STEP * STEP
    s.add_rows([({"__name__": "ringm", "i": str(i), "g": f"g{i % 3}"},
                 t0 + j * SCRAPE, float(j + i))
                for i in range(NS) for j in range(N0)])
    s.force_flush()
    end0 = t0 + ((N0 - 1) * SCRAPE // STEP + 1) * STEP
    return s, end0


def _ingest(s, end_ms, lo=0, hi=NS, bump=0.0):
    s.add_rows([({"__name__": "ringm", "i": str(i), "g": f"g{i % 3}"},
                 end_ms - STEP + (k + 1) * SCRAPE,
                 float(2000 + bump + i + k))
                for i in range(lo, hi) for k in range(4)])


def _cold(s, q, start, end):
    return exec_query(EvalConfig(start=start, end=end, step=STEP,
                                 storage=s, disable_cache=True), q)


@pytest.fixture(autouse=True)
def _fresh_cache():
    rrc.GLOBAL.reset()
    yield
    rrc.GLOBAL.reset()
    os.environ.pop("VM_RESULT_CACHE_RING", None)


QUERIES = ["sum by (g)(rate(ringm[5m]))", "rate(ringm[5m])"]


class TestRingServedEqualsCold:
    @pytest.mark.parametrize("q", QUERIES)
    def test_rolling_refreshes(self, tmp_path, q):
        s, end = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        inp0 = metricslib.REGISTRY.counter(
            "vm_rollup_cache_inplace_total").get()
        start = end - DUR
        api._exec_range_cached(EvalConfig(start=start, end=end, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        for r in range(4):
            end += STEP
            start = end - DUR
            _ingest(s, end, bump=r)
            served = api._exec_range_cached(
                EvalConfig(start=start, end=end, step=STEP, storage=s), q,
                int(time.time() * 1000))
            assert _sha(served) == _sha(_cold(s, q, start, end)), \
                f"refresh {r} diverged from cold"
        assert metricslib.REGISTRY.counter(
            "vm_rollup_cache_inplace_total").get() > inp0
        s.close()

    def test_new_series_appears_and_vanishes(self, tmp_path):
        q = QUERIES[0]
        s, end = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        start = end - DUR
        api._exec_range_cached(EvalConfig(start=start, end=end, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        # series i=0 vanishes, i=NS..NS+3 appear mid-window
        for r in range(3):
            end += STEP
            start = end - DUR
            _ingest(s, end, lo=1, hi=NS + 4, bump=10 * r)
            served = api._exec_range_cached(
                EvalConfig(start=start, end=end, step=STEP, storage=s), q,
                int(time.time() * 1000))
            assert _sha(served) == _sha(_cold(s, q, start, end))
        s.close()

    def test_backfill_resets_and_recovers(self, tmp_path):
        q = QUERIES[0]
        s, end = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        start = end - DUR
        api._exec_range_cached(EvalConfig(start=start, end=end, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        # backfill far behind the OFFSET window -> cache reset
        s.add_rows([({"__name__": "ringm", "i": "0", "g": "g0"},
                     end - 3 * DUR, 1.0)])
        assert rrc.GLOBAL.stats()["entries"] == 0
        for r in range(2):
            end += STEP
            start = end - DUR
            _ingest(s, end, bump=50 + r)
            served = api._exec_range_cached(
                EvalConfig(start=start, end=end, step=STEP, storage=s), q,
                int(time.time() * 1000))
            assert _sha(served) == _sha(_cold(s, q, start, end))
        s.close()

    @pytest.mark.parametrize("age_ms,emptied", [(3 * DUR, True),
                                                (rrc.OFFSET_MS // 2, False)])
    @pytest.mark.parametrize("path", ["add_rows", "add_rows_columnar"])
    def test_write_listener_resets_on_backfill_only(self, tmp_path, path,
                                                    age_ms, emptied):
        """Storage publishes each batch's oldest timestamp and knows no
        cache; the cache's own listener keeps the rule: a batch reaching
        back past OFFSET_MS empties a populated GLOBAL, a fresher one
        leaves it, on both ingest paths."""
        s, end = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        api._exec_range_cached(
            EvalConfig(start=end - DUR, end=end, step=STEP, storage=s),
            QUERIES[0], int(time.time() * 1000))
        assert rrc.GLOBAL.stats()["entries"] > 0
        ts = int(time.time() * 1000) - age_ms
        if path == "add_rows":
            n = s.add_rows([({"__name__": "ringm", "i": "0", "g": "g0"},
                             ts, 1.0)])
        else:
            from victoriametrics_tpu import native
            n = s.add_rows_columnar(native.parse_prom_columnar(
                b'ringm{i="0",g="g0"} 1 %d\n' % ts, ts))
        assert n == 1
        assert (rrc.GLOBAL.stats()["entries"] == 0) == emptied
        s.close()

    @pytest.mark.parametrize("q", QUERIES)
    def test_ring_on_off_identical_rows(self, tmp_path, q):
        """Acceptance: VM_RESULT_CACHE_RING=0 and =1 produce identical
        rows for the same refresh sequence."""
        shas = {}
        for ring in ("0", "1"):
            os.environ["VM_RESULT_CACHE_RING"] = ring
            rrc.GLOBAL.reset()
            s, end = _mk_store(tmp_path, name=f"ring{ring}-{hash(q) % 97}")
            api = PrometheusAPI(s)
            start = end - DUR
            api._exec_range_cached(
                EvalConfig(start=start, end=end, step=STEP, storage=s), q,
                int(time.time() * 1000))
            seq = []
            for r in range(3):
                end += STEP
                start = end - DUR
                _ingest(s, end, bump=r)  # same data both rounds
                served = api._exec_range_cached(
                    EvalConfig(start=start, end=end, step=STEP,
                               storage=s), q, int(time.time() * 1000))
                seq.append(_sha(served))
                assert _sha(served) == _sha(_cold(s, q, start, end))
            shas[ring] = seq
            s.close()
        assert shas["0"] == shas["1"]


class TestRingEntryMechanics:
    def test_views_stay_valid_across_compaction(self, tmp_path):
        """An in-place merge that compacts into a fresh buffer must not
        corrupt rows returned by the PREVIOUS merge (old buffer intact)."""
        q = QUERIES[0]
        s, end = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        start = end - DUR
        api._exec_range_cached(EvalConfig(start=start, end=end, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        prev = None
        prev_copy = None
        # enough refreshes to exhaust COL_HEADROOM and force a compaction
        for r in range(rrc.COL_HEADROOM + 4):
            end += STEP
            start = end - DUR
            _ingest(s, end, bump=r)
            served = api._exec_range_cached(
                EvalConfig(start=start, end=end, step=STEP, storage=s), q,
                int(time.time() * 1000))
            if prev is not None:
                for ts, want in zip(prev, prev_copy):
                    np.testing.assert_array_equal(ts.values, want)
            prev = served
            prev_copy = [ts.values.copy() for ts in served]
        s.close()

    def test_held_rows_survive_next_merge_with_changed_tail(self, tmp_path):
        """Rows handed out by one merge must stay stable while a LATER
        merge of the same key rewrites the volatile tail (a concurrent
        viewer of the same dashboard still serializing the previous
        response).  A late sample inside the OFFSET window (no cache
        reset) changes the recomputed tail values, so a write-through
        merge would visibly mutate the held rows."""
        q = QUERIES[0]
        s, end = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        start = end - DUR
        api._exec_range_cached(EvalConfig(start=start, end=end, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        end += STEP
        _ingest(s, end)
        held = api._exec_range_cached(
            EvalConfig(start=end - DUR, end=end, step=STEP, storage=s), q,
            int(time.time() * 1000))
        held_copy = [ts.values.copy() for ts in held]
        # late sample in the volatile tail: newer than the entry's
        # coverage (no backfill reset) but inside held's served window,
        # so the next refresh recomputes those columns to NEW values
        s.add_rows([({"__name__": "ringm", "i": "0", "g": "g0"},
                     end - 2 * STEP + 7_000, 99_999.0)])
        end += STEP
        _ingest(s, end, bump=3)
        served = api._exec_range_cached(
            EvalConfig(start=end - DUR, end=end, step=STEP, storage=s), q,
            int(time.time() * 1000))
        for ts, want in zip(held, held_copy):
            np.testing.assert_array_equal(ts.values, want)
        assert _sha(served) == _sha(_cold(s, q, end - DUR, end))
        s.close()

    def test_nonlive_window_refresh_stays_o_suffix(self, tmp_path):
        """A dashboard whose window ends BEFORE now-OFFSET gets a
        single-column tail per refresh, which the HTTP executor widens to
        a 2-point sub-eval.  That sub must not write eval-level cache
        entries under its short window (no_eval_cache, same guard as the
        eval-level suffix subs): a clobbered inner entry forces the next
        refresh into a full-window recompute."""
        q = QUERIES[0]
        s, end = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        end -= 20 * STEP          # well behind now - OFFSET_MS: no tail trim
        dur = 60 * STEP           # suffix fetch (window+lookback ~11min)
        start = end - dur         # stays well under 30% of this window
        cold_ec = EvalConfig(start=start, end=end, step=STEP, storage=s,
                             disable_cache=True)
        exec_query(cold_ec, q)
        cold_samples = cold_ec.samples_scanned
        assert cold_samples > 0
        api._exec_range_cached(EvalConfig(start=start, end=end, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        for r in range(3):
            end += STEP
            start = end - dur
            ec = EvalConfig(start=start, end=end, step=STEP, storage=s)
            served = api._exec_range_cached(ec, q, int(time.time() * 1000))
            assert _sha(served) == _sha(_cold(s, q, start, end))
            assert ec.samples_scanned < 0.3 * cold_samples
            # the clobber is invisible behind the HTTP-level entry: probe
            # the shared eval-level (fused) entry with a direct eval — a
            # sub that replaced it with its 2-column window forces this
            # into a full-window recompute
            ev = EvalConfig(start=start, end=end, step=STEP, storage=s)
            exec_query(ev, q)
            assert ev.samples_scanned < 0.3 * cold_samples, (
                f"refresh {r}: eval-level query scanned "
                f"{ev.samples_scanned} of a {cold_samples}-sample window:"
                f" the widened HTTP tail sub clobbered the shared "
                f"eval-level cache entry")
        s.close()

    def test_full_hit_after_noop_put_is_filtered_and_sorted(self, tmp_path):
        """An in-place merge keeps append-ordered rows in the entry and
        stamps the following put() into a no-op, skipping the caller's
        filter+sort.  A later full hit of the same window must re-apply
        both, or its row order diverges from the partial-hit responses
        and from the VM_RESULT_CACHE_RING=0 oracle."""
        q = "rate(ringm[5m])"
        s, end0 = _mk_store(tmp_path)
        # a series that exists ONLY just after the initial window end:
        # rolling over it appends its row at the END of the ring entry,
        # while its label (i="!!" < "0") sorts FIRST
        s.add_rows([({"__name__": "ringm", "i": "!!", "g": "g0"},
                     end0 - 19 * STEP + k * SCRAPE, float(k))
                    for k in range(8)])
        s.force_flush()
        api = PrometheusAPI(s)
        end = end0 - 20 * STEP    # non-live: no volatile-tail trim
        dur = 30 * STEP
        start = end - dur
        api._exec_range_cached(EvalConfig(start=start, end=end, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        for _ in range(3):        # roll over the "!!" series' samples
            end += STEP
            start = end - dur
            api._exec_range_cached(EvalConfig(start=start, end=end,
                                              step=STEP, storage=s), q,
                                   int(time.time() * 1000))
        # same window again: full hit served straight from the entry
        full = api._exec_range_cached(
            EvalConfig(start=start, end=end, step=STEP, storage=s), q,
            int(time.time() * 1000))
        raws = [ts.raw for ts in full]
        assert any(b'"!!"' in r or b"!!" in r for r in raws)
        assert raws == sorted(raws), \
            "full hit returned entry append order, not the sorted order " \
            "partial hits serve"
        assert not any(np.isnan(ts.values).all() for ts in full)
        assert _sha(full) == _sha(_cold(s, q, start, end))
        s.close()

    def test_merged_rows_are_read_only_views(self, tmp_path):
        q = QUERIES[0]
        s, end = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        start = end - DUR
        api._exec_range_cached(EvalConfig(start=start, end=end, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        end += STEP
        _ingest(s, end)
        served = api._exec_range_cached(
            EvalConfig(start=end - DUR, end=end, step=STEP, storage=s), q,
            int(time.time() * 1000))
        assert served and not served[0].values.flags.writeable
        with pytest.raises((ValueError, RuntimeError)):
            served[0].values[0] = 1.0
        s.close()

    def test_read_only_views_survive_group_join_dup_merge(self):
        """Regression: group_left with duplicate joined names merges the
        'one' side IN PLACE (binary_op mergeNonOverlappingTimeseries); the
        merge destination must own its values, because ring-cache partial
        hits hand the eval read-only views (and copy_shallow_labels shares
        the underlying array)."""
        from victoriametrics_tpu.query.binary_op import eval_binary_op
        from victoriametrics_tpu.query.metricsql.ast import ModifierExpr
        from victoriametrics_tpu.query.types import Timeseries
        from victoriametrics_tpu.storage.metric_name import MetricName

        def ro(vals):
            a = np.array(vals)
            a.setflags(write=False)
            return a

        many = [Timeseries(MetricName(b"m", [(b"instance", b"a")]),
                           ro([1.0, 2.0, 3.0, 4.0]))]
        # same on(instance) signature, join tags leave the joined names
        # identical -> duplicate path; complementary NaN masks -> merge ok
        one = [Timeseries(MetricName(b"o", [(b"instance", b"a"),
                                            (b"le", b"x")]),
                          ro([1.0, np.nan, np.nan, np.nan])),
               Timeseries(MetricName(b"o", [(b"instance", b"a"),
                                            (b"le", b"y")]),
                          ro([np.nan, 2.0, 2.0, 2.0]))]
        out = eval_binary_op("*", many, one, False,
                             ModifierExpr(op="on", args=["instance"]),
                             ModifierExpr(op="group_left"), False)
        assert len(out) == 1
        np.testing.assert_array_equal(out[0].values, [1.0, 4.0, 6.0, 8.0])
        # inputs stay untouched (the merge wrote into an owned copy)
        np.testing.assert_array_equal(one[0].values,
                                      [1.0, np.nan, np.nan, np.nan])

    def test_partial_results_never_committed_inplace(self):
        """A partial suffix (cluster node down) must not mutate the live
        entry: merge takes the pure rebuild path and the entry keeps its
        pre-merge coverage (the never-cache-partial contract)."""
        from victoriametrics_tpu.query.types import new_series
        c = rrc.RollupResultCache(max_entries=8)

        class _St:
            cache_token = 991201

        now = int(time.time() * 1000)
        start = (now - 3600_000) // STEP * STEP
        end = start + 10 * STEP

        def mk_rows(n):
            r = [new_series(np.arange(n, dtype=np.float64),
                            labels=[(b"i", b"0")])]
            for ts in r:
                ts.raw = ts.metric_name.marshal()
            return r

        ec = EvalConfig(start=start, end=end, step=STEP, storage=_St())
        c.put(ec, "q", mk_rows(ec.n_points), now)
        ec2 = EvalConfig(start=start + STEP, end=end + STEP, step=STEP,
                         storage=_St())
        hit, new_start = c.get(ec2, "q", now)
        assert hit is not None and new_start == end + STEP
        gen0 = hit.entry.gen
        c_end0 = hit.entry.c_end
        ec2._partial[0] = True  # the suffix fetch was partial
        fresh = mk_rows(1)
        rows = c.merge(hit, fresh, ec2, new_start, now_ms=now)
        assert len(rows) == 1  # still served
        assert hit.entry.gen == gen0 and hit.entry.c_end == c_end0, \
            "partial suffix was committed into the live entry"

    def test_compaction_prunes_vanished_series_rows(self, tmp_path):
        """Series churn must not grow a hot entry's rows without bound:
        rows whose remaining prefix is all-NaN drop at compaction."""
        q = QUERIES[1]  # per-series rows: rate(ringm[5m])
        s, end = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        start = end - DUR
        api._exec_range_cached(EvalConfig(start=start, end=end, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        # each round retires one series id and mints a new one: constant
        # LIVE cardinality (NS), ever-churning identity
        rounds = 2 * (rrc.COL_HEADROOM + DUR // STEP) + 8
        for r in range(rounds):
            end += STEP
            start = end - DUR
            _ingest(s, end, lo=r + 1, hi=r + 1 + NS, bump=r)
            api._exec_range_cached(
                EvalConfig(start=start, end=end, step=STEP, storage=s), q,
                int(time.time() * 1000))
        key = (s.cache_token, (0, 0), q, STEP)
        with rrc.GLOBAL._lock:
            e = rrc.GLOBAL._cache.get(key)
        assert e is not None
        # without pruning the entry would hold every identity ever seen
        # (NS + rounds rows); with compaction-time pruning it is bounded
        # by live series + the window depth + one headroom's worth of
        # churn since the last compaction
        assert e.n_rows < NS + DUR // STEP + rrc.COL_HEADROOM + 16, \
            f"{e.n_rows} rows cached for {NS} live series"
        assert e.n_rows < NS + rounds  # sanity: strictly better than none
        s.close()

    def test_byte_bound_evicts_lru(self):
        c = rrc.RollupResultCache(max_entries=100, max_bytes=1)

        class _St:
            cache_token = 991199

        now = int(time.time() * 1000)
        start = (now - 3600_000) // STEP * STEP
        end = start + 10 * STEP
        from victoriametrics_tpu.query.types import new_series
        for i in range(5):
            ec = EvalConfig(start=start, end=end, step=STEP, storage=_St())
            rows = [new_series(np.arange(ec.n_points, dtype=np.float64),
                               labels=[(b"i", str(i).encode())])]
            c.put(ec, f"q{i}", rows, now)
        # every entry is over the 1-byte budget: only the MRU one survives
        assert c.entry_count() == 1
        assert c.size_bytes() > 0
        # the limit is exported
        assert c.max_bytes == 1

    def test_put_identity_skip_counts_inplace(self, tmp_path):
        """Repeated puts of an unchanged series set reuse the entry's
        MetricName list (satellite: no per-refresh identity rebuild)."""
        c = rrc.RollupResultCache(max_entries=8)

        class _St:
            cache_token = 991200

        from victoriametrics_tpu.query.types import new_series
        now = int(time.time() * 1000)
        start = (now - 3600_000) // STEP * STEP
        end = start + 10 * STEP
        ec = EvalConfig(start=start, end=end, step=STEP, storage=_St())
        rows = [new_series(np.arange(ec.n_points, dtype=np.float64),
                           labels=[(b"i", b"0")])]
        for ts in rows:
            ts.raw = ts.metric_name.marshal()
        r0 = metricslib.REGISTRY.counter(
            "vm_rollup_cache_put_identity_reused_total").get()
        c.put(ec, "q", rows, now)
        c.put(ec, "q", rows, now)
        assert metricslib.REGISTRY.counter(
            "vm_rollup_cache_put_identity_reused_total").get() > r0


@pytest.mark.race
class TestRingRace:
    def test_concurrent_refreshes_ingest_and_reset(self, tmp_path):
        """Concurrent refreshes, live ingest and a mid-flight backfill
        reset over ONE cache entry: every served result must equal a cold
        eval of its own window (run under VMT_RACETRACE=1 via
        tools/race.sh for the sanitizer pass)."""
        q = QUERIES[0]
        s, end0 = _mk_store(tmp_path)
        api = PrometheusAPI(s)
        start = end0 - DUR
        api._exec_range_cached(EvalConfig(start=start, end=end0, step=STEP,
                                          storage=s), q,
                               int(time.time() * 1000))
        errors: list = []
        compared = [0]
        stop = threading.Event()

        def refresher():
            end = end0
            try:
                for r in range(6):
                    end += STEP
                    st = end - DUR
                    v0 = s.data_version
                    served = api._exec_range_cached(
                        EvalConfig(start=st, end=end, step=STEP,
                                   storage=s), q, int(time.time() * 1000))
                    cold = _cold(s, q, st, end)
                    if s.data_version != v0:
                        continue  # ingest landed between the two evals:
                        #           served/cold saw different data
                    compared[0] += 1
                    if _sha(served) != _sha(cold):
                        errors.append(f"refresh {r} diverged")
            except Exception as e:  # pragma: no cover - failure capture
                errors.append(repr(e))

        def ingester():
            end = end0
            try:
                for r in range(6):
                    end += STEP
                    _ingest(s, end, bump=r)
                    if r == 3:
                        # backfill: resets the cache mid-stream
                        s.add_rows([({"__name__": "ringm", "i": "0",
                                      "g": "g0"}, end0 - 3 * DUR, 1.0)])
                    time.sleep(0.005)
            except Exception as e:  # pragma: no cover - failure capture
                errors.append(repr(e))
            finally:
                stop.set()

        threads = [threading.Thread(target=refresher, daemon=True)
                   for _ in range(2)] + \
                  [threading.Thread(target=ingester, daemon=True)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert compared[0] > 0  # at least some served==cold pairs raced-free
        s.close()


class TestMergeGateServePriority:
    @staticmethod
    def _hold_serving(duration_s: float):
        """Hold a serving section on a SEPARATE thread (a thread inside
        its own serving section is exempt from the yield by design)."""
        from victoriametrics_tpu.utils import workpool
        started = threading.Event()

        def hold():
            with workpool.serving():
                started.set()
                time.sleep(duration_s)

        t = threading.Thread(target=hold, daemon=True)
        t.start()
        started.wait(5)
        return t

    def test_merge_defers_to_serving(self, monkeypatch):
        from victoriametrics_tpu.utils import workpool
        monkeypatch.setenv("VM_MERGE_YIELD_MS", "100")
        gate = workpool.MergeGate(limit=2)
        y0 = gate.yields
        holder = self._hold_serving(5.0)
        t0 = time.perf_counter()
        with gate:
            waited = time.perf_counter() - t0
        # yielded (counted) and resumed within the bounded budget
        assert gate.yields == y0 + 1
        assert 0.08 <= waited < 5.0
        holder.join(timeout=10)
        # no serving in flight: no yield
        t0 = time.perf_counter()
        with gate:
            pass
        assert time.perf_counter() - t0 < 0.08
        assert gate.yields == y0 + 1

    def test_merge_resumes_when_serving_drains(self, monkeypatch):
        from victoriametrics_tpu.utils import workpool
        monkeypatch.setenv("VM_MERGE_YIELD_MS", "5000")
        gate = workpool.MergeGate(limit=2)
        self._hold_serving(0.05)
        t0 = time.perf_counter()
        with gate:
            waited = time.perf_counter() - t0
        # resumed as soon as serving drained, far below the 5s budget
        assert waited < 2.0

    def test_no_yield_on_serving_or_pool_threads(self, monkeypatch):
        """Priority-inversion guard: a thread inside its own serving
        section, or a shared-POOL worker (holding a slot the serve's
        fetch tasks queue behind), must never sleep in the yield."""
        from victoriametrics_tpu.utils import workpool
        monkeypatch.setenv("VM_MERGE_YIELD_MS", "4000")
        gate = workpool.MergeGate(limit=2)
        holder = self._hold_serving(2.5)
        # self-serving thread: no deferral despite serving_busy()
        with workpool.serving():
            t0 = time.perf_counter()
            with gate:
                pass
            assert time.perf_counter() - t0 < 0.5
        # pool worker: flush-style task entering the gate must not stall.
        # submit + sleep so a REAL worker picks the task up (a single-item
        # run() executes inline on this thread, which isn't a worker)
        pool = workpool.WorkPool(workers=2)

        def merge_task():
            assert getattr(workpool._yield_tls, "pool_worker", False)
            t0 = time.perf_counter()
            with gate:
                return time.perf_counter() - t0

        fut = pool.submit(merge_task)
        time.sleep(0.2)
        waited = fut.result()
        assert waited < 0.5
        pool.shutdown()
        holder.join(timeout=10)
