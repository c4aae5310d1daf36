"""Concurrency stress harness for the threaded host plane (the
reference's `-race` CI + synctest role, Makefile test-race): hammer ONE
Storage with concurrent columnar ingest, queries, flushes, merges,
snapshots and deletes under randomized scheduling, with assertion-checked
invariants.

Torn reads are detectable by construction: every written sample satisfies
value == timestamp % 1e9, so any mixed-up (ts, value) pairing, partial
block, or cross-series contamination trips an exact-equality check.
"""

import queue
import random
import threading
import time
import warnings

import numpy as np
import pytest

from victoriametrics_tpu.devtools import locktrace, racetrace
from victoriametrics_tpu.devtools.locktrace import (LockHeldTooLongWarning,
                                                    LockOrderError,
                                                    TracedLock, make_lock)
from victoriametrics_tpu.devtools.racetrace import RaceWarning, traced_fields
from victoriametrics_tpu.devtools.sched import DeterministicScheduler

pytestmark = pytest.mark.race  # the tools/race.sh selection

try:
    from victoriametrics_tpu import native
    from victoriametrics_tpu.query.exec import exec_query
    from victoriametrics_tpu.query.types import EvalConfig
    from victoriametrics_tpu.storage.storage import Storage
    from victoriametrics_tpu.storage.tag_filters import filters_from_dict
except ImportError:  # optional deps (zstandard) missing
    pass

# canonical native gate (conftest skips the marked tests when the codec
# library is unavailable)
needs_native = pytest.mark.requires_native

T0 = 1_753_700_000_000
# T0 is a literal 2025-07-28: storages that merge state a retention that
# does not depend on today's date
RETENTION_MS = 100 * 365 * 86_400_000
DURATION_S = 8.0
N_WRITERS = 2
SERIES_PER_WRITER = 24


def _val(ts_arr):
    return (ts_arr % 1_000_000_000).astype(np.float64)


class _Stress:
    def __init__(self, storage):
        self.storage = storage
        self.stop = threading.Event()
        self.errors: list[BaseException] = []
        self.appended = [0] * N_WRITERS  # samples per writer (monotonic)
        self.lock = threading.Lock()

    def guard(self, fn):
        def run():
            rng = random.Random(id(fn) & 0xFFFF)
            try:
                while not self.stop.is_set():
                    fn(rng)
                    time.sleep(rng.uniform(0, 0.01))  # chaos scheduling
            except BaseException as e:  # noqa: BLE001 - harness boundary
                self.errors.append(e)
                self.stop.set()
        return run

    # -- workers ---------------------------------------------------------

    def writer(self, w):
        step = [0]
        keys = [f'stress{{w="{w}",i="{i}"}}'.encode()
                for i in range(SERIES_PER_WRITER)]
        keybuf = b"".join(keys)
        klens = np.fromiter((len(k) for k in keys), np.int64, len(keys))
        koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])

        def run(rng):
            k = rng.randint(1, 6)  # scrapes per series this batch
            base = T0 + step[0] * 15_000
            step[0] += k
            ts = (base + np.arange(k, dtype=np.int64)[None, :] * 15_000 +
                  w)  # writer-unique phase: series never collide
            ts = np.broadcast_to(ts, (len(keys), k)).reshape(-1).copy()
            cr = native.ColumnarRows(
                keybuf, np.repeat(koffs, k), np.repeat(klens, k),
                ts, _val(ts))
            self.storage.add_rows_columnar(cr)
            with self.lock:
                self.appended[w] += k
        return run

    def reader(self, rng):
        w = rng.randrange(N_WRITERS)
        cols = self.storage.search_columns(
            filters_from_dict({"__name__": "stress", "w": str(w)}),
            T0 - 10**6, T0 + 10**10)
        for s in range(cols.n_series):
            n = int(cols.counts[s])
            ts = cols.ts[s, :n]
            vals = cols.vals[s, :n]
            assert bool((np.diff(ts) > 0).all()), \
                "timestamps not strictly increasing"
            np.testing.assert_array_equal(vals, _val(ts))

    def querier(self, rng):
        rows = exec_query(
            EvalConfig(start=T0, end=T0 + 4_000_000, step=60_000,
                       storage=self.storage, tpu=None,
                       disable_cache=bool(rng.getrandbits(1))),
            'count(last_over_time(stress[10m]))')
        for ts in rows:
            v = ts.values[np.isfinite(ts.values)]
            assert bool((v <= N_WRITERS * SERIES_PER_WRITER).all())

    def flusher(self, rng):
        if rng.random() < 0.3:
            self.storage.force_merge()
        else:
            self.storage.force_flush()

    def snapshotter(self, rng):
        name = self.storage.create_snapshot()
        time.sleep(rng.uniform(0, 0.02))
        assert self.storage.delete_snapshot(name)

    def deleter(self, rng):
        # disposable series: create then delete; must never affect the
        # stress/metric invariants
        self.storage.add_rows(
            [({"__name__": "victim", "i": str(rng.randrange(4))},
              T0 + rng.randrange(10**6), 1.0)])
        self.storage.delete_series(
            filters_from_dict({"__name__": "victim"}))


@needs_native
def test_concurrent_ingest_query_flush_snapshot(tmp_path):
    s = Storage(str(tmp_path / "s"), retention_ms=RETENTION_MS)
    st = _Stress(s)
    workers = [st.guard(st.writer(w)) for w in range(N_WRITERS)]
    workers += [st.guard(st.reader), st.guard(st.querier),
                st.guard(st.flusher), st.guard(st.snapshotter),
                st.guard(st.deleter)]
    threads = [threading.Thread(target=f, daemon=True) for f in workers]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    while time.monotonic() - t0 < DURATION_S and not st.stop.is_set():
        time.sleep(0.1)
    st.stop.set()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "stress worker wedged (deadlock?)"
    if st.errors:
        raise st.errors[0]
    # final invariant: exactly the appended samples are durable and
    # correct after a full flush+merge
    s.force_flush()
    s.force_merge()
    for w in range(N_WRITERS):
        cols = s.search_columns(
            filters_from_dict({"__name__": "stress", "w": str(w)}),
            T0 - 10**6, T0 + 10**10)
        assert cols.n_series == SERIES_PER_WRITER
        expected = st.appended[w]
        for i in range(cols.n_series):
            n = int(cols.counts[i])
            assert n == expected, (w, i, n, expected)
            ts = cols.ts[i, :n]
            np.testing.assert_array_equal(cols.vals[i, :n], _val(ts))
    s.close()

# -- runtime lock-order tracing (devtools/locktrace) -------------------------


class TestLockTrace:
    def test_cycle_detected_fails_fast(self):
        """A->B in one thread then B->A in another must raise
        LockOrderError promptly — the whole point is that the synthetic
        deadlock FAILS instead of hanging the suite."""
        g = locktrace.LockGraph()
        a = TracedLock("stress.A", graph=g, mode="raise")
        b = TracedLock("stress.B", graph=g, mode="raise")
        phase1_done = threading.Event()
        errors: list[BaseException] = []

        def t1():
            with a:
                with b:
                    pass
            phase1_done.set()

        def t2():
            assert phase1_done.wait(10)
            try:
                with b:
                    with a:  # reverse order: potential ABBA deadlock
                        pass
            except LockOrderError as e:
                errors.append(e)

        threads = [threading.Thread(target=t1, daemon=True),
                   threading.Thread(target=t2, daemon=True)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15)
            assert not t.is_alive(), "locktrace test wedged"
        assert time.monotonic() - t0 < 15
        assert len(errors) == 1
        assert "stress.A" in str(errors[0]) and "stress.B" in str(errors[0])

    def test_consistent_order_is_quiet(self):
        g = locktrace.LockGraph()
        a = TracedLock("q.A", graph=g)
        b = TracedLock("q.B", graph=g)
        for _ in range(3):
            with a:
                with b:
                    pass
        assert g.edges() == {"q.A": {"q.B"}}

    def test_rlock_reentry_and_nonreentrant_self_deadlock(self):
        g = locktrace.LockGraph()
        r = TracedLock("q.R", graph=g, reentrant=True)
        with r:
            with r:  # fine: RLock semantics
                assert r.locked()
        plain = TracedLock("q.P", graph=g)
        with plain:
            with pytest.raises(LockOrderError, match="re-acquired"):
                plain.acquire()

    def test_failed_trylock_leaves_no_phantom_edge(self):
        """hold A, try-lock B, fail, retake in the safe B->A order: the
        aborted attempt must not have poisoned the graph."""
        g = locktrace.LockGraph()
        a = TracedLock("t.A", graph=g)
        b = TracedLock("t.B", graph=g)
        acquired, release = threading.Event(), threading.Event()

        def holder():
            with b:
                acquired.set()
                release.wait(10)

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        assert acquired.wait(10)
        with a:
            assert b.acquire(blocking=False) is False  # contended: aborts
        release.set()
        t.join(10)
        assert "t.B" not in g.edges().get("t.A", set())
        with b:
            with a:  # safe order must stay legal
                pass

    def test_cycle_abort_rolls_back_partial_edges(self):
        """When acquiring C while holding A and B raises on the B->C
        cycle, the A->C edge recorded a moment earlier must be rolled
        back too — C->A later is legitimate."""
        g = locktrace.LockGraph()
        a = TracedLock("r.A", graph=g)
        b = TracedLock("r.B", graph=g)
        c = TracedLock("r.C", graph=g)
        with c:
            with b:  # establishes C->B
                pass
        with a:
            with b:
                with pytest.raises(LockOrderError):
                    c.acquire()  # A->C recorded, then B->C finds cycle
        assert "r.C" not in g.edges().get("r.A", set())
        with c:
            with a:  # must stay legal
                pass

    def test_cross_thread_handoff_reacquire(self):
        lk = TracedLock("t.H", graph=locktrace.LockGraph())
        lk.acquire()
        t = threading.Thread(target=lk.release)
        t.start(); t.join()
        lk.acquire()  # stale stack entry must be purged, not fatal
        lk.release()

    def test_held_too_long_warns(self):
        lk = TracedLock("q.slow", graph=locktrace.LockGraph(),
                        max_hold_ms=1.0)
        with pytest.warns(LockHeldTooLongWarning):
            with lk:
                time.sleep(0.02)

    def test_factory_injects_traced_locks(self, monkeypatch):
        monkeypatch.setenv("VMT_LOCKTRACE", "1")
        assert isinstance(locktrace.make_lock("x"), TracedLock)
        assert isinstance(locktrace.make_rlock("x"), TracedLock)
        monkeypatch.setenv("VMT_LOCKTRACE", "0")
        if racetrace.enabled():
            # the racetrace sanitizer also claims the factory seam
            assert isinstance(locktrace.make_lock("x"), TracedLock)
        else:
            assert isinstance(locktrace.make_lock("x"),
                              type(threading.Lock()))

    @needs_native
    def test_storage_lock_hierarchy_under_tracing(self, tmp_path,
                                                  monkeypatch):
        """The real ingest/flush path runs clean under the tracer: the
        Table -> Partition -> flush-mutex hierarchy is acyclic."""
        monkeypatch.setenv("VMT_LOCKTRACE", "1")
        s = Storage(str(tmp_path / "lt"), retention_ms=RETENTION_MS)
        t0 = 1_753_700_000_000
        s.add_rows([({"__name__": "lt", "i": str(i)}, t0 + i * 1000, 1.0)
                    for i in range(32)])
        s.force_flush()
        s.force_merge()
        assert len(s.search_series(
            filters_from_dict({"__name__": "lt"}), t0 - 1, t0 + 10**6)) == 32
        s.close()

# -- happens-before race sanitizer (devtools/racetrace) -----------------------


@pytest.fixture
def race_on(monkeypatch):
    """Sanitizer on for the test body; restores prior state after (no-op
    teardown when the whole run came in via tools/race.sh with
    VMT_RACETRACE=1)."""
    monkeypatch.setenv("VMT_LOCKTRACE_MAX_HOLD_MS", "60000")
    was = racetrace.enabled()
    racetrace.enable()
    racetrace.reset()
    yield racetrace
    racetrace.reset()
    if not was:
        racetrace.disable()


@traced_fields("n")
class _Scratch:
    """The seeded-race fixture: one traced int, no lock."""

    def __init__(self):
        self.n = 0
        self.d = {}


class TestRaceTrace:
    def test_seeded_race_is_detected_with_both_stacks(self, race_on):
        """Two unjoined threads bump the same unsynchronized field: a
        happens-before race EXISTS regardless of how the OS interleaves
        them, so detection is deterministic — no lucky timing needed."""
        b = _Scratch()

        def bump():
            for _ in range(4):
                b.n = b.n + 1
                b.d["k"] = b.d.get("k", 0) + 1  # dict update, same story

        ts = [threading.Thread(target=bump) for _ in range(2)]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RaceWarning)
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        reps = racetrace.reports()
        assert reps, "unsynchronized cross-thread access not reported"
        r = reps[0]
        assert r.field == "n" and r.cls_name == "_Scratch"
        assert r.kind in ("write-write", "read-write", "write-read")
        first = "".join(str(f) for f in r.first_stack.format())
        second = "".join(str(f) for f in r.second_stack.format())
        assert "test_stress_race" in first and "bump" in first
        assert "test_stress_race" in second and "bump" in second
        assert r.first_thread != r.second_thread

    def test_report_counted_in_registry(self, race_on):
        from victoriametrics_tpu.utils import metrics as metricslib
        c = metricslib.REGISTRY.counter("vm_race_reports_total")
        before = c.get()
        b = _Scratch()
        ts = [threading.Thread(target=lambda: setattr(b, "n", b.n + 1))
              for _ in range(2)]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RaceWarning)
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        assert c.get() > before

    def test_make_lock_synchronized_twin_is_clean(self, race_on):
        b = _Scratch()
        lk = make_lock("race.scratch._lock")
        assert isinstance(lk, TracedLock)  # racetrace reached the seam

        def bump():
            for _ in range(8):
                with lk:
                    b.n = b.n + 1

        ts = [threading.Thread(target=bump) for _ in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert racetrace.reports() == []
        assert b.n == 24

    def test_queue_handoff_is_clean(self, race_on):
        b = _Scratch()
        q = queue.Queue()

        def producer():
            b.n = 41
            q.put("ready")

        def consumer():
            q.get()
            b.n = b.n + 1

        t1 = threading.Thread(target=producer)
        t2 = threading.Thread(target=consumer)
        t1.start()
        t2.start()
        t1.join()
        t2.join()
        assert racetrace.reports() == []
        assert b.n == 42

    def test_thread_start_join_create_edges(self, race_on):
        b = _Scratch()
        b.n = 1                       # parent write before fork

        def child():
            b.n += 1                  # ordered after start()

        t = threading.Thread(target=child)
        t.start()
        t.join()
        b.n += 1                      # ordered after join()
        assert racetrace.reports() == []
        assert b.n == 3

    def test_disabled_is_plain_attribute(self, monkeypatch):
        """With the sanitizer off, traced classes carry no descriptor:
        no overhead where nobody asked for the trace."""
        if racetrace.enabled():
            pytest.skip("suite running under VMT_RACETRACE=1")
        monkeypatch.setenv("VMT_LOCKTRACE", "0")
        assert not isinstance(_Scratch.__dict__.get("n"),
                              racetrace._TracedField)
        try:
            from victoriametrics_tpu.storage.partition import Partition
        except ImportError:          # zstandard absent: storage not loadable
            Partition = None
        if Partition is not None:
            assert not isinstance(Partition.__dict__.get("_pending"),
                                  racetrace._TracedField)
        assert isinstance(make_lock("x"), type(threading.Lock()))


# -- deterministic interleaving scheduler (devtools/sched) --------------------


class TestDeterministicScheduler:
    def _racy_run(self, seed):
        racetrace.reset()
        sched = DeterministicScheduler(seed=seed, change_prob=0.3)
        b = _Scratch()

        def bump():
            for _ in range(6):
                b.n = b.n + 1

        for i in range(3):
            sched.spawn(f"w{i}", bump)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RaceWarning)
            sched.run(timeout=30)
        reps = racetrace.reports()
        pairs = [(r.field, r.kind, r.first_thread, r.second_thread)
                 for r in reps]
        return sched.trace, pairs

    def test_same_seed_replays_same_interleaving_and_reports(self, race_on):
        """The acceptance property: the seed IS the interleaving.  Two
        runs with one seed produce the identical traced-point schedule and
        the identical race reports; the report's seed is therefore a full
        reproducer."""
        t1, p1 = self._racy_run(1234)
        t2, p2 = self._racy_run(1234)
        assert t1 == t2
        assert p1 == p2
        assert p1, "the seeded racy workload must be flagged"
        assert len(t1) > 10

    def test_locked_workload_is_clean_and_deterministic(self, race_on):
        def run(seed):
            racetrace.reset()
            sched = DeterministicScheduler(seed=seed, change_prob=0.3)
            b = _Scratch()
            lk = make_lock("sched.locked._lock")

            def bump():
                for _ in range(6):
                    with lk:
                        b.n = b.n + 1

            for i in range(3):
                sched.spawn(f"w{i}", bump)
            sched.run(timeout=30)
            return sched.trace, b.n, racetrace.reports()

        t1, n1, r1 = run(77)
        t2, n2, r2 = run(77)
        assert t1 == t2 and n1 == n2 == 18
        assert r1 == [] and r2 == []
        # lock contention descheduled someone at least once
        assert any(x.endswith("/blocked") for x in t1)

    def test_workpool_runs_inline_under_scheduler(self, race_on):
        """A scheduled thread's pool batches execute INLINE (pool workers
        are not turnstile participants), so the interleaving stays a pure
        function of the seed: two runs with one seed produce identical
        traces and identical results."""
        from victoriametrics_tpu.utils.workpool import WorkPool

        pool = WorkPool(workers=4)

        def run(seed):
            racetrace.reset()
            sched = DeterministicScheduler(seed=seed, change_prob=0.3)
            b = _Scratch()
            lk = make_lock("sched.pool._lock")
            logs = {}

            def body(w):
                def job(j):
                    with lk:
                        b.n = b.n + 1
                    return (w, j, threading.current_thread().name)
                got = pool.run([lambda j=j: job(j) for j in range(4)])
                logs[w] = got

            for i in range(3):
                sched.spawn(f"w{i}", body, i)
            sched.run(timeout=60)
            return sched.trace, b.n, dict(logs), racetrace.reports()

        t1, n1, l1, r1 = run(321)
        t2, n2, l2, r2 = run(321)
        assert t1 == t2 and n1 == n2 == 12
        assert l1 == l2
        # inline: every job ran on its submitting (scheduled) thread
        for w, got in l1.items():
            assert [g[:2] for g in got] == [(w, j) for j in range(4)]
            assert all(g[2] == f"w{w}" for g in got)
        assert r1 == [] and r2 == []
        assert pool._threads == []   # the pool never started workers

    @needs_native
    @pytest.mark.parametrize("assemble", ["1", "0"])
    def test_parallel_fetch_stress_racetrace_clean(self, tmp_path, race_on,
                                                   monkeypatch, assemble):
        """The concurrent fetch stress with the WORK POOL engaged: several
        reader threads fan multi-part collection across pool workers while
        a writer appends and a flusher compacts — the sanitizer must stay
        silent and every read must satisfy the value == f(ts) invariant.
        Runs once with the fused native assemble kernel (the per-part
        vm_assemble_part calls race on the _dec memo + budget seams) and
        once on the split Python oracle path."""
        monkeypatch.setenv("VM_SEARCH_WORKERS", "2")
        monkeypatch.setenv("VM_NATIVE_ASSEMBLE", assemble)
        s = Storage(str(tmp_path / "pf"))
        keys = [f'pfetch{{i="{i}"}}'.encode() for i in range(16)]
        keybuf = b"".join(keys)
        klens = np.fromiter((len(k) for k in keys), np.int64, len(keys))
        koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])

        def append(step, k):
            ts = (T0 + (step + np.arange(k, dtype=np.int64))[None, :]
                  * 15_000)
            ts = np.broadcast_to(ts, (len(keys), k)).reshape(-1).copy()
            s.add_rows_columnar(native.ColumnarRows(
                keybuf, np.repeat(koffs, k), np.repeat(klens, k),
                ts, _val(ts)))

        # seed several file parts so readers fan >1 unit per query
        for p in range(3):
            append(p * 8, 8)
            s.force_flush()

        stop = threading.Event()
        errors: list[BaseException] = []

        def guard(fn):
            def run():
                try:
                    i = 0
                    while not stop.is_set() and i < 40:
                        fn(i)
                        i += 1
                except BaseException as e:  # noqa: BLE001 — harness edge
                    errors.append(e)
                    stop.set()
            return run

        def reader(_i):
            cols = s.search_columns(
                filters_from_dict({"__name__": "pfetch"}),
                T0 - 10**6, T0 + 10**10)
            for r in range(cols.n_series):
                n = int(cols.counts[r])
                np.testing.assert_array_equal(cols.vals[r, :n],
                                              _val(cols.ts[r, :n]))

        def writer(i):
            append(24 + i, 2)

        def flusher(i):
            if i % 4 == 0:
                s.force_flush()

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", LockHeldTooLongWarning)
            threads = [threading.Thread(target=f, daemon=True)
                       for f in (guard(reader), guard(reader),
                                 guard(writer), guard(flusher))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive(), "parallel fetch stress wedged"
        if errors:
            raise errors[0]
        assert racetrace.reports() == [], "\n\n".join(
            r.format() for r in racetrace.reports())
        s.close()

    @needs_native
    def test_sharded_ingest_query_stress_racetrace_clean(self, tmp_path,
                                                         race_on,
                                                         monkeypatch):
        """The striped WRITE path under the sanitizer: concurrent
        columnar + legacy writers fan registration stripes and pending
        conversions across the pool (VM_INGEST_SHARDS=4) while readers
        fetch and a flusher compacts — zero race reports, and every read
        satisfies the value == f(ts) invariant.  VM_INGEST_SHARDS=1 is
        the bisection escape hatch (tools/race.sh notes)."""
        monkeypatch.setenv("VM_INGEST_SHARDS", "4")
        monkeypatch.setenv("VM_SEARCH_WORKERS", "2")
        s = Storage(str(tmp_path / "si"))
        keys = [f'shing{{i="{i}"}}'.encode() for i in range(16)]
        keybuf = b"".join(keys)
        klens = np.fromiter((len(k) for k in keys), np.int64, len(keys))
        koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])

        stop = threading.Event()
        errors: list[BaseException] = []

        def guard(fn):
            def run():
                try:
                    i = 0
                    while not stop.is_set() and i < 30:
                        fn(i)
                        i += 1
                except BaseException as e:  # noqa: BLE001 — harness edge
                    errors.append(e)
                    stop.set()
            return run

        def col_writer(i):
            k = 4
            ts = (T0 + (i * k + np.arange(k, dtype=np.int64))[None, :]
                  * 15_000)
            ts = np.broadcast_to(ts, (len(keys), k)).reshape(-1).copy()
            s.add_rows_columnar(native.ColumnarRows(
                keybuf, np.repeat(koffs, k), np.repeat(klens, k),
                ts, _val(ts)))

        def leg_writer(i):
            ts = T0 + i * 15_000 + 7_000
            s.add_rows([({"__name__": "shleg", "i": str(j)}, ts,
                         float(ts % 1_000_000_000)) for j in range(8)])

        def reader(_i):
            cols = s.search_columns(
                filters_from_dict({"__name__": "shing"}),
                T0 - 10**6, T0 + 10**10)
            for r in range(cols.n_series):
                n = int(cols.counts[r])
                np.testing.assert_array_equal(cols.vals[r, :n],
                                              _val(cols.ts[r, :n]))

        def flusher(i):
            if i % 5 == 0:
                s.force_flush()

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", LockHeldTooLongWarning)
            threads = [threading.Thread(target=f, daemon=True)
                       for f in (guard(col_writer), guard(col_writer),
                                 guard(leg_writer), guard(reader),
                                 guard(flusher))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive(), "sharded ingest stress wedged"
        if errors:
            raise errors[0]
        assert racetrace.reports() == [], "\n\n".join(
            r.format() for r in racetrace.reports())
        s.close()

    @needs_native
    def test_sharded_ingest_inline_under_scheduler(self, tmp_path,
                                                   race_on, monkeypatch):
        """With the deterministic scheduler driving the threads, the
        sharded write path must execute INLINE (no pool workers) and
        stay clean: same seed == same interleaving."""
        monkeypatch.setenv("VM_INGEST_SHARDS", "4")
        s = Storage(str(tmp_path / "sched"))

        def writer(w):
            for j in range(5):
                s.add_rows([({"__name__": "sw", "w": str(w), "j": str(j)},
                             T0 + j * 1000 + w, float(j))])

        sched = DeterministicScheduler(seed=77, change_prob=0.2)
        sched.spawn("w0", writer, 0)
        sched.spawn("w1", writer, 1)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", LockHeldTooLongWarning)
            sched.run(timeout=120)
        assert racetrace.reports() == [], "\n\n".join(
            r.format() for r in racetrace.reports())
        res = s.search_series(filters_from_dict({"__name__": "sw"}),
                              T0 - 10**6, T0 + 10**9)
        assert len(res) == 10
        s.close()

    @needs_native
    def test_partition_and_mergeset_stress_clean_under_scheduler(
            self, tmp_path, race_on):
        """The real LSM paths — partition ingest/flush/merge/read and
        mergeset add/flush/search — run under seeded preemption with the
        sanitizer on and produce ZERO race reports."""
        from victoriametrics_tpu.storage import mergeset
        from victoriametrics_tpu.storage.partition import Partition
        from victoriametrics_tpu.storage.tsid import TSID

        part = Partition(str(tmp_path / "p"), "2025_07")
        mtab = mergeset.Table(str(tmp_path / "m"))
        t0 = 1_753_700_000_000

        def writer(w):
            for i in range(6):
                tsid = TSID(metric_group_id=1, metric_id=w * 100 + i)
                part.add_rows([(tsid, t0 + i * 1000 + w, float(i))])
                mtab.add_items([b"k%02d_%03d" % (w, i)])

        def flusher():
            for _ in range(3):
                part.flush_to_disk()
                mtab.flush_to_disk()

        def reader():
            for _ in range(4):
                _ = part.rows
                list(part.iter_blocks())
                mtab.first_with_prefix(b"k00")
                list(mtab.search_prefix(b"k01"))

        sched = DeterministicScheduler(seed=4242, change_prob=0.2)
        sched.spawn("w0", writer, 0)
        sched.spawn("w1", writer, 1)
        sched.spawn("flush", flusher)
        sched.spawn("read", reader)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", LockHeldTooLongWarning)
            sched.run(timeout=120)
        assert racetrace.reports() == [], "\n\n".join(
            r.format() for r in racetrace.reports())
        part.flush_to_disk()
        assert part.rows == 12
        assert sum(1 for _ in mtab.iter_from(b"")) == 12
        part.close()
        mtab.close()
