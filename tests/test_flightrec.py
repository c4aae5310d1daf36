"""Flight recorder (utils/flightrec): ring mechanics, cross-thread
context/tracer propagation through the work pool (the PR-4/5 gap), the
capture format (Chrome trace-event JSON), the slow-query log, the
queue_wait fetch phase, gc visibility, and the HTTP surface on both
vmsingle ('all') and vmselect ('select') role compositions.

The race-marked stress (concurrent writers + concurrent captures) runs
under VMT_RACETRACE=1 via tools/race.sh.
"""

from __future__ import annotations

import gc
import json
import threading
import time

import pytest

from victoriametrics_tpu.utils import flightrec
from victoriametrics_tpu.utils import metrics as metricslib
from victoriametrics_tpu.utils import querytracer

try:
    # the storage stack itself is the gate: ops/compress falls back to
    # zlib when the zstandard package is absent, so these run either way
    import victoriametrics_tpu.storage.storage  # noqa: F401
    _STORAGE_ERR = None
except ImportError as e:
    _STORAGE_ERR = e

needs_storage = pytest.mark.skipif(
    _STORAGE_ERR is not None,
    reason=f"storage deps unavailable: {_STORAGE_ERR}")

T0 = 1_753_700_000_000


@pytest.fixture(autouse=True)
def _recorder_enabled(monkeypatch):
    """Every test starts with the recorder ON and a clean thread ctx;
    tests that flip VM_FLIGHTREC call reconfigure() themselves and the
    teardown re-reads the restored env."""
    monkeypatch.delenv("VM_FLIGHTREC", raising=False)
    flightrec.reconfigure()
    flightrec.clear_ctx()
    yield
    flightrec.clear_ctx()
    monkeypatch.undo()
    flightrec.reconfigure()


class TestRing:
    def test_rec_and_capture_roundtrip(self):
        rec = flightrec.FlightRecorder(max_captures=4)
        t0 = time.perf_counter()
        time.sleep(0.002)
        flightrec.rec("t:roundtrip", t0, time.perf_counter() - t0,
                      arg="hello")
        cap = rec.capture("test", window_s=5.0)
        evs = [e for e in cap["trace"]["traceEvents"]
               if e["name"] == "t:roundtrip"]
        assert evs, "recorded span missing from capture"
        ev = evs[0]
        assert ev["ph"] == "X" and ev["dur"] >= 2_000  # µs
        assert ev["args"]["arg"] == "hello"
        assert cap["n_events"] >= 1 and cap["n_threads"] >= 1
        # the whole trace must be JSON-serializable (Perfetto-loadable)
        json.dumps(cap["trace"])

    def test_instant_event_format(self):
        rec = flightrec.FlightRecorder(max_captures=4)
        flightrec.instant("t:decision", arg="rebuild")
        cap = rec.capture("test", window_s=5.0)
        evs = [e for e in cap["trace"]["traceEvents"]
               if e["name"] == "t:decision"]
        assert evs and evs[0]["ph"] == "i" and "dur" not in evs[0]
        assert evs[0]["s"] == "t"

    def test_ring_wraparound_keeps_newest(self, monkeypatch):
        """A lapped ring keeps the LAST cap events; the overwritten ones
        are counted into vm_flight_dropped_events_total at capture."""
        monkeypatch.setenv("VM_FLIGHTREC_EVENTS", "8")
        out = {}

        def run():
            base = time.perf_counter()
            for k in range(20):
                flightrec.rec(f"wrap:{k}", base + k * 1e-7, 1e-8)
            out["ring"] = flightrec._tls.ring

        t = threading.Thread(target=run)
        t.start()
        t.join(10)
        ring = out["ring"]
        assert ring.cap == 8 and ring.i == 20
        names = [e[2] for e in ring.snapshot(0.0)]
        # cap=8 retains cursors 12..19; the seqlock filter drops the
        # oldest retained cursor too (it is the one slot a mid-store
        # writer could be tearing — conservative, never misattributing)
        assert names == [f"wrap:{k}" for k in range(13, 20)]
        dropped = metricslib.REGISTRY.counter(
            "vm_flight_dropped_events_total")
        d0 = dropped.get()
        flightrec.FlightRecorder(max_captures=2).capture(
            "test", window_s=60.0)
        # 20 written, 8 retained, none previously captured -> >= 12
        # (other threads' rings may contribute more, never less)
        assert dropped.get() - d0 >= 12

    def test_ring_grows_to_what_its_thread_records(self):
        """A ring starts small and doubles as its thread records: every
        event before the first wrap survives each doubling, in order,
        and growth stops at the capacity."""
        out = {}

        def run():
            base = time.perf_counter()
            out["size0"] = None
            for k in range(700):
                flightrec.rec(f"grow:{k}", base + k * 1e-7, 1e-8, None,
                              k % 3)
                if k == 0:
                    out["size0"] = flightrec._tls.ring.size
            out["ring"] = flightrec._tls.ring

        t = threading.Thread(target=run)
        t.start()
        t.join(10)
        ring = out["ring"]
        assert out["size0"] == flightrec._INITIAL_SLOTS < ring.cap
        assert ring.size == 1024 and ring.mask == 1023 and ring.i == 700
        snap = ring.snapshot(0.0)
        assert [e[2] for e in snap] == [f"grow:{k}" for k in range(700)]
        assert [e[6] for e in snap] == [k % 3 for k in range(700)]
        assert all(len(lst) == ring.size for lst in (
            ring.t0, ring.dur, ring.name, ring.ctx, ring.arg, ring.depth))

    def test_taken_is_first_uncaptured_cursor(self, monkeypatch):
        """After a capture, ring.taken points at the first cursor NOT
        yet captured — so a later wrap past already-captured events
        reports zero drops (the off-by-one counted the last captured
        event as lost once per wrap: false drops on a lossless ring)."""
        monkeypatch.setenv("VM_FLIGHTREC_EVENTS", "8")
        out = {}

        def run():
            base = time.perf_counter()
            for k in range(6):
                flightrec.rec(f"taken:{k}", base + k * 1e-7, 1e-8)
            out["ring"] = flightrec._tls.ring

        t = threading.Thread(target=run)
        t.start()
        t.join(10)
        flightrec.FlightRecorder(max_captures=2).capture(
            "test", window_s=60.0)
        ring = out["ring"]
        assert ring.i == 6
        assert ring.taken == 6, \
            "taken must be first-uncaptured (last captured cursor + 1)"

    def test_capture_merge_is_timestamp_ordered(self):
        """Events from different thread rings interleave in ts order in
        the merged trace (Perfetto requires no ordering, but the summary
        and human eyes do)."""
        now = time.perf_counter()
        offs = {"ordtest:a0": 1e-4, "ordtest:a1": 3e-4,
                "ordtest:b0": 0.0, "ordtest:b1": 2e-4}

        def writer(names):
            for n in names:
                flightrec.rec(n, now - 0.01 + offs[n], 1e-4)

        ta = threading.Thread(target=writer,
                              args=(["ordtest:a0", "ordtest:a1"],))
        tb = threading.Thread(target=writer,
                              args=(["ordtest:b0", "ordtest:b1"],))
        for t in (ta, tb):
            t.start()
        for t in (ta, tb):
            t.join(10)
        cap = flightrec.FlightRecorder(max_captures=2).capture(
            "test", window_s=5.0)
        ours = [e for e in cap["trace"]["traceEvents"]
                if e["name"].startswith("ordtest:")]
        assert [e["name"] for e in ours] == \
            ["ordtest:b0", "ordtest:a0", "ordtest:b1", "ordtest:a1"]
        ts = [e["ts"] for e in ours]
        assert ts == sorted(ts)

    def test_disabled_is_a_noop(self, monkeypatch):
        monkeypatch.setenv("VM_FLIGHTREC", "0")
        flightrec.reconfigure()
        assert not flightrec.enabled()
        n_rings = len(flightrec._rings)

        def run():
            # rec() must return before touching TLS: no ring is created
            flightrec.rec("off:span", time.perf_counter(), 1e-3)
            flightrec.instant("off:instant")

        t = threading.Thread(target=run)
        t.start()
        t.join(10)
        assert len(flightrec._rings) == n_rings
        assert flightrec.FlightRecorder(max_captures=2).capture(
            "test") is None

    def test_dead_thread_rings_are_reclaimed(self):
        """A dead thread's ring stays capturable while its events are
        inside the retention window, then is pruned — per-connection
        handler threads must not leak one ring each forever."""
        old_t0 = time.perf_counter() - 7200.0
        fresh_t0 = time.perf_counter()
        rings = {}

        def run(key, t0):
            flightrec.rec(f"reclaim:{key}", t0, 1e-3)
            rings[key] = flightrec._tls.ring

        # "old" created LAST: nothing prunes it between creation and
        # the capture below (ring creation prunes stale dead rings too)
        for key, t0 in (("fresh", fresh_t0), ("old", old_t0)):
            t = threading.Thread(target=run, args=(key, t0))
            t.start()
            t.join(10)
        with flightrec._rings_lock:
            assert rings["old"] in flightrec._rings
        # a capture prunes dead rings past the retention window: the
        # stale ring goes, the recent one survives
        flightrec.FlightRecorder(max_captures=2).capture(
            "test", window_s=5.0)
        with flightrec._rings_lock:
            assert rings["old"] not in flightrec._rings
            assert rings["fresh"] in flightrec._rings

    def test_capture_ring_is_bounded(self):
        rec = flightrec.FlightRecorder(max_captures=2)
        flightrec.instant("t:x")
        ids = [rec.capture("test", window_s=5.0)["id"] for _ in range(3)]
        listed = [c["id"] for c in rec.list()]
        assert listed == [ids[2], ids[1]]      # newest first, oldest gone
        assert rec.get(ids[0]) is None
        assert rec.get(ids[2])["id"] == ids[2]
        # list() metadata excludes the trace body
        assert all("trace" not in c for c in rec.list())


class TestSummary:
    def test_overlap_attribution_excludes_own_work(self):
        """The slow-refresh summary charges OTHER-context work
        overlapping the serve window, bucketed by category prefix —
        including ambient work on the serve thread itself (a gc pause
        stalling the refresh is interference, not the query's work)."""
        evs = [
            {"name": "serve:refresh", "ph": "X", "pid": 1, "tid": 1,
             "ts": 0.0, "dur": 100_000.0, "args": {"ctx": 7}},
            # other thread, no ctx: full 50ms inside the window
            {"name": "merge:part", "ph": "X", "pid": 1, "tid": 2,
             "ts": 10_000.0, "dur": 50_000.0},
            # SAME thread as the serve, ctx 0: a gc pause on the serving
            # thread counts — the tid is not an exclusion criterion
            {"name": "gc:gen0", "ph": "X", "pid": 1, "tid": 1,
             "ts": 40_000.0, "dur": 10_000.0},
            # the query's OWN fetch work (same ctx): excluded
            {"name": "fetch:rollup", "ph": "X", "pid": 1, "tid": 3,
             "ts": 0.0, "dur": 30_000.0, "args": {"ctx": 7}},
            # partial overlap: only the first 5ms counts
            {"name": "gc:gen2", "ph": "X", "pid": 1, "tid": 4,
             "ts": 95_000.0, "dur": 20_000.0},
            # instant events never contribute duration
            {"name": "rcache:inplace", "ph": "i", "pid": 1, "tid": 1,
             "ts": 5.0, "s": "t"},
            # pure waits are deference, not interference: a merge
            # sleeping in the serve-priority yield must NOT be charged
            # as merge overlap — it goes to the waiting bucket
            {"name": "merge:yield", "ph": "X", "pid": 1, "tid": 5,
             "ts": 0.0, "dur": 80_000.0},
            {"name": "fetch:queue_wait", "ph": "X", "pid": 1, "tid": 6,
             "ts": 20_000.0, "dur": 30_000.0},
            # nested fan spans (flush:table contains its workers'
            # flush:part): per-category interval UNION, not a sum —
            # coverage can never exceed the refresh window
            {"name": "flush:table", "ph": "X", "pid": 1, "tid": 7,
             "ts": 10_000.0, "dur": 60_000.0},
            {"name": "flush:part", "ph": "X", "pid": 1, "tid": 8,
             "ts": 15_000.0, "dur": 50_000.0},
        ]
        s = flightrec.summarize(evs)
        assert s["slow_refresh"]["ms"] == 100.0
        assert s["slow_refresh"]["ctx"] == 7
        assert s["slow_refresh"]["overlap_ms_by_category"] == \
            {"merge": 50.0, "gc": 15.0, "flush": 60.0}
        assert s["slow_refresh"]["waiting_ms_by_name"] == \
            {"merge:yield": 80.0, "fetch:queue_wait": 30.0}
        assert s["span_ms_by_name"]["merge:part"] == 50.0

    def test_focus_ctx_pins_the_triggering_refresh(self):
        """A slow-refresh capture explains the refresh that TRIPPED it,
        even when a bigger serve span (the cold first eval) shares the
        window; unknown ctx falls back to the slowest serve."""
        evs = [
            # the cold first eval: huge, ctx 1, nothing overlaps it
            {"name": "serve:refresh", "ph": "X", "pid": 1, "tid": 1,
             "ts": 0.0, "dur": 900_000.0, "args": {"ctx": 1}},
            # the triggering steady refresh: ctx 5, later, smaller
            {"name": "serve:refresh", "ph": "X", "pid": 1, "tid": 1,
             "ts": 1_000_000.0, "dur": 200_000.0, "args": {"ctx": 5}},
            {"name": "flush:part", "ph": "X", "pid": 1, "tid": 2,
             "ts": 1_050_000.0, "dur": 100_000.0},
        ]
        s = flightrec.summarize(evs, focus_ctx=5)
        assert s["slow_refresh"]["ctx"] == 5
        assert s["slow_refresh"]["ms"] == 200.0
        assert s["slow_refresh"]["overlap_ms_by_category"] == \
            {"flush": 100.0}
        # no focus (on-demand): slowest serve wins
        assert flightrec.summarize(evs)["slow_refresh"]["ctx"] == 1
        # stale focus (refresh span already aged out): fall back too
        assert flightrec.summarize(
            evs, focus_ctx=99)["slow_refresh"]["ctx"] == 1


class TestCrossThreadPropagation:
    def test_pool_worker_inherits_ctx_and_tracer(self, monkeypatch):
        """A task submitted to the shared pool runs under the SUBMITTING
        query's flight context and tracer: its spans land in ctx_events
        and its trace children attach to the query's tree (the PR-4/5
        propagation gap this PR closes)."""
        from victoriametrics_tpu.utils import workpool
        monkeypatch.setenv("VM_SEARCH_WORKERS", "2")
        ctx = flightrec.new_ctx()
        prev_ctx = flightrec.set_ctx(ctx)
        tracer = querytracer.Tracer("query root")
        prev_tr = querytracer.set_current(tracer)
        started = threading.Event()
        release = threading.Event()
        info = {}
        main_tid = threading.get_ident()

        def task():
            started.set()
            release.wait(10)
            info["tid"] = threading.get_ident()
            info["ctx"] = flightrec.get_ctx()
            with querytracer.current().new_child("worker side") as c:
                c.donef("ok")
            with flightrec.phase("t:worker"):
                time.sleep(0.001)
            return 42

        try:
            fut = workpool.POOL.submit(task)
            # the main thread has NOT entered result() yet, so the task
            # is necessarily running on a pool worker thread
            assert started.wait(10), "pool never started the task"
            release.set()
            assert fut.result() == 42
        finally:
            querytracer.set_current(prev_tr)
            flightrec.set_ctx(prev_ctx)
        assert info["tid"] != main_tid
        assert info["ctx"] == ctx
        # the worker's span is reassembled under the query's ctx ...
        evs = flightrec.ctx_events(ctx)
        by_name = {name for _t0, _dur, name, _tid in evs}
        assert "t:worker" in by_name
        assert "pool:task" in by_name           # the pool's own task span
        assert "pool:queue_wait" in by_name     # and its queue wait
        worker_tids = {tid for _t0, _dur, name, tid in evs
                       if name == "t:worker"}
        assert worker_tids == {info["tid"]}
        # ... the phase split sums it ...
        split = flightrec.phase_split(ctx)
        assert split.get("t:worker", 0.0) > 0.0
        # ... and the tracer child attached to the submitting tree
        d = tracer.to_dict()
        msgs = [c["message"] for c in d.get("children", ())]
        assert "worker side: ok" in msgs

    def test_ctx_restored_after_task(self, monkeypatch):
        """Workers must not leak a finished task's ctx into the next."""
        from victoriametrics_tpu.utils import workpool
        monkeypatch.setenv("VM_SEARCH_WORKERS", "2")
        ctx = flightrec.new_ctx()
        prev = flightrec.set_ctx(ctx)
        try:
            workpool.POOL.run([lambda: None] * 4)
        finally:
            flightrec.set_ctx(prev)
        seen = []
        done = threading.Event()

        def probe():
            seen.append(flightrec.get_ctx())
            done.set()

        flightrec.clear_ctx()
        workpool.POOL.submit(probe).result()
        assert done.wait(10)
        assert seen == [0]


class TestQueueWaitPhase:
    def test_search_gate_wait_ticks_queue_wait_phase(self):
        """Time spent queued at the SearchGate lands in
        vm_fetch_phase_seconds_total{phase="queue_wait"} (the previously
        invisible slice: without it the phase split doesn't sum to
        contended wall time)."""
        from victoriametrics_tpu.utils.workpool import SearchGate
        qw = metricslib.REGISTRY.float_counter(
            'vm_fetch_phase_seconds_total{phase="queue_wait"}')
        v0 = qw.get()
        gate = SearchGate(limit=1, max_queue_ms=5000)
        release = threading.Event()
        entered = threading.Event()

        def hold():
            with gate:
                entered.set()
                release.wait(10)

        t = threading.Thread(target=hold, daemon=True)
        t.start()
        assert entered.wait(10)
        t2_done = threading.Event()

        def queued():
            with gate:
                t2_done.set()

        t2 = threading.Thread(target=queued, daemon=True)
        t2.start()
        time.sleep(0.05)        # let the second caller actually queue
        release.set()
        assert t2_done.wait(10)
        t.join(10)
        t2.join(10)
        assert qw.get() - v0 >= 0.03
        # and the wait is visible on the flight timeline
        cap = flightrec.FlightRecorder(max_captures=2).capture(
            "test", window_s=5.0)
        assert any(e["name"] == "fetch:queue_wait"
                   for e in cap["trace"]["traceEvents"])


class TestGcVisibility:
    def test_gc_pause_metrics_and_flight_event(self):
        pause = metricslib.REGISTRY.float_counter(
            "vm_gc_pause_seconds_total")
        p0 = pause.get()
        gc.collect()
        assert pause.get() > p0
        # per-generation collection counts in the exposition
        text = metricslib.REGISTRY.write_prometheus()
        assert 'vm_gc_collections_total{gen="0"}' in text
        assert 'vm_gc_collections_total{gen="2"}' in text
        assert "# TYPE vm_gc_pause_seconds_total counter" in text
        # and the pause is a span on the flight timeline
        cap = flightrec.FlightRecorder(max_captures=2).capture(
            "test", window_s=5.0)
        assert any(e["name"].startswith("gc:gen")
                   for e in cap["trace"]["traceEvents"])


class TestSlowQueryLog:
    def test_threshold_and_ring(self):
        from victoriametrics_tpu.query.querystats import SlowQueryLog
        log = SlowQueryLog(max_records=2, threshold_ms=10.0)
        total = metricslib.REGISTRY.counter("vm_slow_queries_total")
        t0 = total.get()
        assert not log.maybe_record("fast", 0, 1, 15, (0, 0), 0.001)
        assert log.snapshot() == []
        assert log.maybe_record("slow1", 0, 1, 15, (0, 0), 0.5)
        assert log.maybe_record("slow2", 0, 1, 15, (0, 0), 0.6,
                                capture_id=7)
        assert log.maybe_record("slow3", 0, 1, 15, None, 0.7)
        assert total.get() - t0 == 3
        snap = log.snapshot()                    # newest first, bounded
        assert [r["query"] for r in snap] == ["slow3", "slow2"]
        assert snap[1]["flightCaptureId"] == 7
        assert "flightCaptureId" not in snap[0]
        assert snap[0]["tenant"] == "0:0"

    def test_phase_split_from_flight_ctx(self):
        from victoriametrics_tpu.query.querystats import SlowQueryLog
        log = SlowQueryLog(max_records=4, threshold_ms=1.0)
        ctx = flightrec.new_ctx()
        prev = flightrec.set_ctx(ctx)
        try:
            t0 = time.perf_counter()
            time.sleep(0.002)
            flightrec.rec("fetch:index_search", t0,
                          time.perf_counter() - t0)
        finally:
            flightrec.set_ctx(prev)
        assert log.maybe_record("q", 0, 1, 15, (0, 0), 0.05, ctx=ctx)
        rec0 = log.snapshot()[0]
        assert rec0["phaseSplitMs"].get("fetch:index_search", 0.0) >= 1.0


@pytest.mark.race
class TestRaceStress:
    def test_concurrent_writers_and_captures(self):
        """Writers hammer their rings while captures walk them: the
        seqlock-reader discipline must never produce a torn event or an
        unserializable trace (race-marked; tools/race.sh runs this under
        VMT_RACETRACE=1)."""
        errs = []
        stop = threading.Event()

        def writer(k):
            try:
                n = 0
                while not stop.is_set() and n < 20_000:
                    with flightrec.phase(f"race:w{k}", arg=n):
                        n += 1
                    flightrec.instant(f"race:i{k}")
            except Exception as e:  # noqa: BLE001 — reported below
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(k,), daemon=True)
                   for k in range(4)]
        rec = flightrec.FlightRecorder(max_captures=4)
        for t in threads:
            t.start()
        try:
            for _ in range(10):
                cap = rec.capture("race", window_s=5.0)
                json.dumps(cap["trace"])        # serializable every time
                for ev in cap["trace"]["traceEvents"]:
                    assert ev["ph"] in ("X", "i", "M")
        finally:
            stop.set()
            for t in threads:
                t.join(10)
        assert not errs


# -- HTTP surface -------------------------------------------------------------


@pytest.fixture()
def app(tmp_path):
    """In-process vmsingle (same shape as test_vmsingle_http.app)."""
    from tests.apptest_helpers import Client
    from victoriametrics_tpu.apps.vmsingle import build, parse_flags
    args = parse_flags([f"-storageDataPath={tmp_path}/data",
                        "-httpListenAddr=127.0.0.1:0"])
    storage, srv, api = build(args)
    srv.start()
    yield Client(srv.port)
    srv.stop()
    storage.close()


def _ingest(app, name="fm", n=3):
    lines = "".join(f'{name}{{i="{k}"}} {k} {T0 + j * 15_000}\n'
                    for k in range(n) for j in range(20))
    code, _ = app.post("/api/v1/import/prometheus", lines.encode())
    assert code == 204


@needs_storage
class TestHTTPFlight:
    def test_capture_list_fetch_and_errors(self, app):
        code, body = app.get("/api/v1/status/flight", capture="1")
        assert code == 200
        data = json.loads(body)
        cap_id = data["captured"]
        assert any(c["id"] == cap_id for c in data["data"])
        # fetch-by-id returns the bare Chrome trace-event object
        code, body = app.get("/api/v1/status/flight", id=str(cap_id))
        assert code == 200
        trace = json.loads(body)
        assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
        for ev in trace["traceEvents"]:
            assert {"name", "ph", "pid", "tid"} <= set(ev)
            if ev["ph"] == "X":
                assert "ts" in ev and "dur" in ev
        # the list never inlines trace bodies
        code, body = app.get("/api/v1/status/flight")
        assert code == 200
        lst = json.loads(body)["data"]
        assert lst and all("trace" not in c for c in lst)
        assert all("summary" in c for c in lst)
        code, _ = app.get("/api/v1/status/flight", id="bogus")
        assert code == 422
        code, _ = app.get("/api/v1/status/flight", id="99999999")
        assert code == 404

    def test_disabled_returns_503(self, app, monkeypatch):
        monkeypatch.setenv("VM_FLIGHTREC", "0")
        flightrec.reconfigure()
        try:
            code, _ = app.get("/api/v1/status/flight")
            assert code == 503
        finally:
            monkeypatch.delenv("VM_FLIGHTREC")
            flightrec.reconfigure()

    def test_slow_query_log_links_flight_capture(self, app, monkeypatch):
        """A served query over the slow thresholds produces (1) a
        slow-query record with a cross-thread per-phase split and (2) a
        linked flight capture whose timeline contains the serve span."""
        _ingest(app)
        monkeypatch.setenv("VM_SLOW_QUERY_MS", "0.000001")
        monkeypatch.setenv("VM_SLOW_REFRESH_MS", "0.000001")
        res = app.query_range("fm", T0 / 1e3, (T0 + 300_000) / 1e3, 15)
        assert res["status"] == "success"
        code, body = app.get("/api/v1/status/slow_queries")
        assert code == 200
        data = json.loads(body)
        assert data["status"] == "ok"
        recs = [r for r in data["data"] if r["query"] == "fm"]
        assert recs, "slow query not recorded"
        rec0 = recs[0]
        assert rec0["durationSeconds"] > 0
        assert rec0["phaseSplitMs"], "no per-phase split reassembled"
        # containers (the whole refresh, pool task wrappers) are split
        # out so phaseSplitMs holds disjoint phases, not double counts
        assert "serve:refresh" in rec0.get("containerSpansMs", {})
        assert not any(k in ("serve:refresh", "pool:task")
                       for k in rec0["phaseSplitMs"])
        cap_id = rec0.get("flightCaptureId")
        assert cap_id is not None, "slow refresh tripped no capture"
        code, body = app.get("/api/v1/status/flight", id=str(cap_id))
        assert code == 200
        names = {e["name"] for e in json.loads(body)["traceEvents"]}
        assert "serve:refresh" in names

    def test_fast_queries_stay_out_of_the_log(self, app, monkeypatch):
        _ingest(app, name="fastm")
        monkeypatch.setenv("VM_SLOW_QUERY_MS", "1e9")
        app.query_range("fastm", T0 / 1e3, (T0 + 300_000) / 1e3, 15)
        code, body = app.get("/api/v1/status/slow_queries")
        data = json.loads(body)
        assert not [r for r in data["data"] if r["query"] == "fastm"]
        assert data["thresholdMs"] == 1e9


@needs_storage
class TestSelectModeHTTP:
    def test_select_role_serves_flight_and_slowlog(self, tmp_path):
        """The vmselect role composition (register(mode='select'))
        carries both status endpoints too — they live in
        _register_select, exactly like the reference's vmselect-only
        status handlers."""
        from tests.apptest_helpers import Client
        from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
        from victoriametrics_tpu.httpapi.server import HTTPServer
        from victoriametrics_tpu.storage.storage import Storage
        s = Storage(str(tmp_path / "data"))
        srv = HTTPServer("127.0.0.1", 0)
        PrometheusAPI(s).register(srv, mode="select")
        srv.start()
        try:
            c = Client(srv.port)
            code, body = c.get("/api/v1/status/flight", capture="1")
            assert code == 200
            cap_id = json.loads(body)["captured"]
            code, body = c.get("/api/v1/status/flight", id=str(cap_id))
            assert code == 200 and "traceEvents" in json.loads(body)
            code, body = c.get("/api/v1/status/slow_queries")
            assert code == 200
            data = json.loads(body)
            assert data["status"] == "ok" and "thresholdMs" in data
        finally:
            srv.stop()
            s.close()
