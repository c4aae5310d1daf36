"""Crash-consistency + part-integrity harness (the recovery counterpart
of PR 9's liveness chaos suite).

Three layers:

1. **Torn-part matrix** (tier-1): truncate / bit-flip each of the four
   data-part files plus metadata.json, reopen, and assert the part is
   QUARANTINED loudly — moved to ``quarantine/``, counted in
   ``vm_parts_quarantined_total``, listed at
   ``/api/v1/status/quarantine``, every result flagged partial.  This
   doubles as the regression test that the OLD behavior — a listed part
   that fails to open being logged once and silently dropped from every
   future result — is gone.

2. **Crashpoint matrix** (tier-1): a subprocess ingest/flush/merge/
   snapshot loop is hard-killed (``os._exit`` via the ``crash`` fault
   action) at each named seam of the part lifecycle, then the store is
   reopened and checked against the recovery invariants: opens clean,
   every sample acked before the last successful flush is present
   byte-exact, no orphan ``.tmp`` dirs, no unlisted part dirs, no
   quarantine (a clean kill can lose un-acked work but never tear
   fsynced bytes).

3. **Randomized kill -9 matrix** (``slow`` + ``crash`` markers,
   tools/chaos.sh): the same subprocess storm killed with SIGKILL at
   random instants, >= 20 cycles against one accumulating store.

Plus the storage-side deadline unit tests (typed abort, RPC wire
marker, no node-down marking) for ROADMAP item 3's named leftover.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from tests.apptest_helpers import REPO, Client
from victoriametrics_tpu.devtools import faultinject
from victoriametrics_tpu.storage.metric_name import MetricName
from victoriametrics_tpu.storage.storage import (DeadlineExceededError,
                                                 Storage)
from victoriametrics_tpu.storage.tag_filters import TagFilter

T0 = 1_753_700_000_000
N_SERIES = 8
NAME_FILTER = [TagFilter(b"", b"crashm")]

# ---------------------------------------------------------------------------
# child program: ingest/flush loop that dies at armed crashpoints
# ---------------------------------------------------------------------------

_CHILD_SRC = r"""
import os, sys
sys.path.insert(0, os.getcwd())
from victoriametrics_tpu.storage.storage import Storage
from victoriametrics_tpu.storage.metric_name import MetricName

data_dir, ack_path, scenario, n_batches, t_base = sys.argv[1:6]
n_batches = int(n_batches)
T0 = int(t_base)
N_SERIES = 8

acked = -1
try:
    with open(ack_path) as f:
        lines = [ln for ln in f.read().splitlines() if ln]
    if lines:
        acked = int(lines[-1])
except FileNotFoundError:
    pass

# T0 is a literal 2025-07-28: a stated retention, so that no merge drops
# it whatever today's date is
kw = {"retention_ms": 100 * 365 * 86_400_000}
if scenario == "retention":
    kw["retention_ms"] = 40 * 86_400_000
elif scenario == "downsample":
    kw["downsample"] = "1d:5m"
s = Storage(data_dir, **kw)
names = [MetricName.from_dict({"__name__": "crashm", "s": str(i)})
         for i in range(N_SERIES)]
if scenario == "retention":
    # out-of-retention month: its partition + month index table exist so
    # enforce_retention has something to rotate (indexdb:rotate seam)
    import time as _t
    t_old = int(_t.time() * 1000) - 100 * 86_400_000
    s.add_rows([(MetricName.from_dict({"__name__": "oldm", "s": str(i)}),
                 t_old, float(i)) for i in range(4)])
    s.force_flush()

ackf = open(ack_path, "a")
stormers = []
if scenario == "storm":
    # racing flush/merge/snapshot threads (the PR-9 ingest-storm shape):
    # the randomized SIGKILL lands wherever it lands
    import threading

    def churn():
        while True:
            try:
                s.force_merge()
                s.create_snapshot()
            except Exception:
                # benign churn races (two threads picking one snapshot
                # name, merge vs close) must not fail the child with a
                # non-kill exit code; the SIGKILL is the only exit
                pass
    for _ in range(2):
        th = threading.Thread(target=churn, daemon=True)
        th.start()
        stormers.append(th)

for b in range(acked + 1, acked + 1 + n_batches):
    rows = [(names[i], T0 + b * 1000, float(i * 1_000_000 + b))
            for i in range(N_SERIES)]
    # one fresh series per batch: every flush has NEW index items, so
    # the mergeset/indexdb seams fire each cycle (not only on batch 0)
    rows.append((MetricName.from_dict({"__name__": "churn",
                                       "b": str(b)}),
                 T0 + b * 1000, float(b)))
    s.add_rows(rows)
    s.force_flush()   # durable: data part + index, fsync + rename + dirsync
    ackf.write(f"{b}\n")
    ackf.flush()
    os.fsync(ackf.fileno())
    if scenario == "merge" and b % 2 == 1:
        s.force_merge()
    elif scenario == "snapshot" and b % 2 == 1:
        s.create_snapshot()
    elif scenario == "retention" and b % 2 == 1:
        s.enforce_retention()
    elif scenario == "downsample" and b % 2 == 1:
        # fresh AGED samples each cycle so every run_downsample_cycle has
        # an uncovered (covered, cutoff] range to rewrite — the seam
        # between tier-part publication and the tier.json commit fires
        # on every odd batch, not only the first
        t_hi = T0 - 5 * 86_400_000 + b * 600_000
        s.add_rows([(MetricName.from_dict({"__name__": "agedm",
                                           "s": str(i)}),
                     t_hi - i * 300_000, float(i)) for i in range(3)])
        s.force_flush()
        s.run_downsample_cycle(now_ms=t_hi + 86_400_000 + 300_000)
s.close()
os._exit(0)
"""


def _t_base(scenario: str) -> int:
    # the retention scenario needs IN-retention (recent) sample times —
    # T0 is over a year old and would itself be retention-dropped; the
    # base is fixed per test run and shared child/verifier via argv
    if scenario == "retention":
        return (int(time.time() * 1000) - 2 * 86_400_000) // 1000 * 1000
    return T0


def _run_child(data_dir, ack_path, scenario, n_batches, faults="",
               t_base: int = T0):
    env = dict(os.environ)
    env["VM_FAULTS"] = faults
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, "-c", _CHILD_SRC, str(data_dir), str(ack_path),
         scenario, str(n_batches), str(t_base)],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def _read_acked(ack_path) -> list[int]:
    try:
        with open(ack_path) as f:
            return [int(x) for x in f.read().splitlines() if x]
    except FileNotFoundError:
        return []


def _assert_acked_present(storage: Storage, acked: list[int],
                          t_base: int = T0):
    """Every sample acked before the last successful flush must be
    present BYTE-EXACT after recovery (value encodes (series, batch))."""
    if not acked:
        return
    lo, hi = t_base, t_base + (max(acked) + 1) * 1000
    series = storage.search_series(NAME_FILTER, lo, hi)
    got: dict[tuple[int, int], float] = {}
    for sd in series:
        si = int(dict(sd.metric_name.labels)[b"s"])
        for ts, v in zip(sd.timestamps, sd.values):
            got[(si, int((ts - t_base) // 1000))] = float(v)
    for b in acked:
        for i in range(N_SERIES):
            v = got.get((i, b))
            assert v is not None, \
                f"acked sample (series {i}, batch {b}) LOST after recovery"
            assert v == float(i * 1_000_000 + b), \
                f"acked sample (series {i}, batch {b}) corrupted: {v}"


def _assert_disk_invariants(data_dir: str):
    """Post-recovery disk state: no orphan tmp dirs anywhere, every part
    dir inside a partition is either listed in parts.json or lives in
    the quarantine dir."""
    for root, dirs, _files in os.walk(data_dir):
        for n in dirs:
            assert not n.endswith(".tmp"), \
                f"orphan tmp dir survived recovery: {os.path.join(root, n)}"
    droot = os.path.join(data_dir, "data")
    if not os.path.isdir(droot):
        return
    for pname in os.listdir(droot):
        pdir = os.path.join(droot, pname)
        if not os.path.isdir(pdir):
            continue
        manifest = os.path.join(pdir, "parts.json")
        listed = []
        if os.path.exists(manifest):
            with open(manifest) as f:
                listed = json.load(f)["parts"]
        for n in os.listdir(pdir):
            if not os.path.isdir(os.path.join(pdir, n)):
                continue
            if n.startswith("ds_"):
                # downsampled tier dir: every part dir inside must be
                # listed in the tier's own manifest (tier.json) — the
                # crash seam between part publication and the manifest
                # commit must never leak an unlisted dir past recovery
                tdir = os.path.join(pdir, n)
                tman = os.path.join(tdir, "tier.json")
                tlisted = []
                if os.path.exists(tman):
                    with open(tman) as f:
                        tlisted = json.load(f)["parts"]
                for tn in os.listdir(tdir):
                    if not os.path.isdir(os.path.join(tdir, tn)):
                        continue
                    assert tn in tlisted, \
                        f"unlisted tier part survived recovery: {tdir}/{tn}"
                continue
            assert n in listed or n == "quarantine", \
                f"unlisted part dir survived recovery: {pdir}/{n}"


def _verify_recovery(data_dir, ack_path, retention=False,
                     t_base: int = T0):
    """Reopen the store and check every recovery invariant; returns the
    acked batch list for extra assertions."""
    acked = _read_acked(ack_path)
    kw = {"retention_ms": (40 if retention else 100 * 365) * 86_400_000}
    s = Storage(str(data_dir), **kw)
    try:
        # crash injection never tears fsynced bytes: quarantine must stay
        # empty (it fires only when bytes are actually corrupt)
        assert s.quarantine_report() == [], s.quarantine_report()
        assert s.last_partial is False
        _assert_acked_present(s, acked, t_base)
    finally:
        s.close()
    _assert_disk_invariants(str(data_dir))
    return acked


# ---------------------------------------------------------------------------
# 1. torn-part matrix (tier-1)
# ---------------------------------------------------------------------------

def _build_store(tmp_path, n_batches=3):
    d = str(tmp_path / "store")
    s = Storage(d)
    names = [MetricName.from_dict({"__name__": "crashm", "s": str(i)})
             for i in range(N_SERIES)]
    for b in range(n_batches):
        s.add_rows([(names[i], T0 + b * 1000, float(i * 1_000_000 + b))
                    for i in range(N_SERIES)])
    s.force_flush()
    s.close()
    return d


def _find_data_part(d):
    droot = os.path.join(d, "data")
    for pname in sorted(os.listdir(droot)):
        pdir = os.path.join(droot, pname)
        if not os.path.isdir(pdir):
            continue
        with open(os.path.join(pdir, "parts.json")) as f:
            listed = json.load(f)["parts"]
        if listed:
            return os.path.join(pdir, listed[0])
    raise AssertionError("no file part found")


def _corrupt(path: str, mode: str):
    size = os.path.getsize(path)
    assert size > 0, f"{path} is empty; matrix needs real bytes"
    if mode == "truncate":
        with open(path, "r+b") as f:
            f.truncate(size // 2)
    else:  # bitflip
        with open(path, "r+b") as f:
            f.seek(size // 2)
            b = f.read(1)
            f.seek(size // 2)
            f.write(bytes([b[0] ^ 0x10]))


@pytest.mark.parametrize("mode", ["truncate", "bitflip"])
@pytest.mark.parametrize("fname", ["timestamps.bin", "values.bin",
                                   "index.bin", "metaindex.bin",
                                   "metadata.json"])
def test_torn_part_is_quarantined(tmp_path, fname, mode):
    """A torn/bit-flipped part file is detected at open, the part moves
    to quarantine/, the counter ticks, and the store serves PARTIAL —
    never the old silent drop."""
    from victoriametrics_tpu.storage.partition import _PARTS_QUARANTINED
    d = _build_store(tmp_path)
    part = _find_data_part(d)
    _corrupt(os.path.join(part, fname), mode)
    before = _PARTS_QUARANTINED.get()
    s = Storage(d)
    try:
        rep = s.quarantine_report()
        assert len(rep) == 1 and rep[0]["store"] == "storage", rep
        assert os.path.isdir(rep[0]["path"])
        assert "quarantine" in rep[0]["path"]
        assert not os.path.exists(part), "corrupt part left in place"
        assert _PARTS_QUARANTINED.get() == before + 1
        # the loud-partial regression assert: results flag partial
        assert s.last_partial is True
        # the flushed rows lived in that one part: the query result is
        # missing them AND says so (the old behavior returned the same
        # empty result with partial=False — silent data loss)
        series = s.search_series(NAME_FILTER, T0, T0 + 100_000)
        assert series == []
        assert s.last_partial is True
    finally:
        s.close()
    # partiality survives a restart until the operator acts
    s2 = Storage(d)
    try:
        assert s2.last_partial is True
        assert s2.quarantine_report()
    finally:
        s2.close()


def test_torn_mergeset_part_is_quarantined(tmp_path):
    """Recovery parity: the indexdb's mergeset parts get the same
    verify-at-open + quarantine treatment as data parts."""
    d = _build_store(tmp_path)
    gdir = os.path.join(d, "indexdb", "global")
    part = next(n for n in sorted(os.listdir(gdir))
                if n.startswith("part_"))
    _corrupt(os.path.join(gdir, part, "items.bin"), "bitflip")
    s = Storage(d)
    try:
        rep = s.quarantine_report()
        assert [q["store"] for q in rep] == ["mergeset"], rep
        assert s.last_partial is True
    finally:
        s.close()


def test_quarantine_status_endpoint(tmp_path):
    """/api/v1/status/quarantine lists quarantined parts, and query
    responses over the same server carry isPartial=true."""
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.httpapi.server import HTTPServer
    d = _build_store(tmp_path)
    _corrupt(os.path.join(_find_data_part(d), "values.bin"), "bitflip")
    s = Storage(d)
    srv = HTTPServer("127.0.0.1", 0)
    PrometheusAPI(s).register(srv, mode="select")
    srv.start()
    try:
        c = Client(srv.port)
        code, body = c.get("/api/v1/status/quarantine")
        assert code == 200
        data = json.loads(body)["data"]
        assert data["count"] == 1 and data["partial"] is True
        assert data["quarantined"][0]["store"] == "storage"
        # the regression assert at the HTTP surface: the query names the
        # loss instead of silently serving an empty complete result
        code, body = c.get("/api/v1/query", query="count(crashm)",
                           time=str((T0 + 30_000) // 1000))
        assert code == 200
        assert json.loads(body).get("isPartial") is True
    finally:
        srv.stop()
        s.close()


def test_cluster_quarantine_fanout(tmp_path):
    """The vmselect's /api/v1/status/quarantine is backed by a real RPC
    fan-out (quarantineReport_v1): storage-node quarantines surface at
    the select plane, tagged per node."""
    from victoriametrics_tpu.parallel.cluster_api import (
        ClusterStorage, StorageNodeClient, make_storage_handlers)
    from victoriametrics_tpu.parallel.rpc import HELLO_SELECT, RPCServer
    d = _build_store(tmp_path)
    _corrupt(os.path.join(_find_data_part(d), "index.bin"), "truncate")
    s = Storage(d)
    srv = RPCServer("127.0.0.1", 0, HELLO_SELECT,
                    make_storage_handlers(s))
    srv.start()
    node = StorageNodeClient("127.0.0.1", srv.port, srv.port)
    cs = ClusterStorage([node])
    try:
        rep = cs.quarantine_report()
        assert len(rep) == 1 and rep[0]["store"] == "storage"
        assert rep[0]["node"] == node.name
    finally:
        node.close()
        srv.stop()
        s.close()


def test_clean_store_reports_nothing(tmp_path):
    d = _build_store(tmp_path)
    s = Storage(d)
    try:
        assert s.quarantine_report() == []
        assert s.last_partial is False
        assert len(s.search_series(NAME_FILTER, T0, T0 + 100_000)) == \
            N_SERIES
    finally:
        s.close()


# ---------------------------------------------------------------------------
# 2. crashpoint matrix (tier-1): each armed seam, subprocess, clean reopen
# ---------------------------------------------------------------------------

_SEAMS = [
    ("part:finalize:pre_rename", "flush"),
    ("part:finalize:post_rename", "flush"),
    ("partition:parts_json:pre_replace", "flush"),
    ("merge:post_rename_pre_manifest", "merge"),
    ("downsample:post_rename_pre_manifest", "downsample"),
    ("mergeset:flush", "flush"),
    ("indexdb:rotate", "retention"),
    ("snapshot:mid", "snapshot"),
]


@pytest.mark.parametrize("seam,scenario", _SEAMS,
                         ids=[s for s, _ in _SEAMS])
def test_crashpoint_seam(tmp_path, seam, scenario):
    """kill -9 (os._exit at the armed seam) mid-lifecycle, then reopen:
    acked-before-flush data byte-exact, no tmp orphans, no silent part
    loss, no quarantine."""
    d = tmp_path / "store"
    ack = tmp_path / "acks"
    tb = _t_base(scenario)  # ONE base: child runs + verifier must agree
    # run 1, unfaulted: establish a durable acked baseline
    p = _run_child(d, ack, scenario, 2, t_base=tb)
    out, err = p.communicate(timeout=120)
    assert p.returncode == 0, err.decode()[-2000:]
    baseline = _read_acked(ack)
    assert baseline, "baseline run acked nothing"
    # run 2, armed: must die AT the seam (exit code 86)
    p = _run_child(d, ack, scenario, 50, faults=f"{seam}=crash",
                   t_base=tb)
    out, err = p.communicate(timeout=120)
    assert p.returncode == faultinject.CRASH_EXIT_CODE, \
        (p.returncode, err.decode()[-2000:])
    assert f"CRASH at {seam}" in err.decode()
    acked = _verify_recovery(d, ack, retention=(scenario == "retention"),
                             t_base=tb)
    assert set(baseline) <= set(acked)


# ---------------------------------------------------------------------------
# 3. randomized kill -9 storm (slow; tools/chaos.sh)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.crash
def test_kill9_randomized_matrix(tmp_path):
    """>= 20 SIGKILL cycles at randomized instants against ONE
    accumulating store (recovery-from-recovered-state compounds), with
    flush/merge/snapshot churn racing ingest.  Every cycle must reopen
    with zero invariant violations."""
    rng = np.random.default_rng(0xC0FFEE)
    d = tmp_path / "store"
    ack = tmp_path / "acks"
    cycles = 20
    for cyc in range(cycles):
        before = len(_read_acked(ack))
        p = _run_child(d, ack, "storm", 10_000)
        # wait until the storm makes at least one NEW durable ack, then
        # kill at a randomized instant inside the flush/merge/snapshot
        # churn — progress is guaranteed, the kill point is not
        deadline = time.time() + 20
        while len(_read_acked(ack)) <= before and time.time() < deadline:
            time.sleep(0.02)
        assert len(_read_acked(ack)) > before, \
            f"cycle {cyc}: no durable progress before the kill window"
        time.sleep(float(rng.uniform(0.0, 0.5)))
        p.send_signal(signal.SIGKILL)
        p.communicate(timeout=60)
        assert p.returncode == -signal.SIGKILL
        _verify_recovery(d, ack)
    assert len(_read_acked(ack)) >= cycles, \
        "the storm never made durable progress between kills"


# ---------------------------------------------------------------------------
# storage-side deadline enforcement (ROADMAP item 3 leftover)
# ---------------------------------------------------------------------------

class TestStorageDeadline:
    def test_local_abort_typed_and_counted(self, tmp_path):
        """An expired budget aborts the scan with the typed error and
        ticks vm_storage_deadline_aborts_total."""
        from victoriametrics_tpu.storage.storage import _DEADLINE_ABORTS
        d = _build_store(tmp_path)
        s = Storage(d)
        try:
            before = _DEADLINE_ABORTS.get()
            with pytest.raises(DeadlineExceededError):
                s.search_columns(NAME_FILTER, T0, T0 + 100_000,
                                 deadline=time.monotonic() - 0.001)
            assert _DEADLINE_ABORTS.get() == before + 1
            # no deadline => no budget machinery, full result
            assert s.search_columns(NAME_FILTER, T0,
                                    T0 + 100_000).n_series == N_SERIES
        finally:
            s.close()

    def test_rpc_budget_field_aborts_server_side(self, tmp_path):
        """The shipped budget_ms field alone (no client-side socket
        deadline) makes the storage handler abort mid-flight, within
        ~one check interval once the budget expires."""
        from victoriametrics_tpu.parallel.cluster_api import (
            _write_filters, make_storage_handlers)
        from victoriametrics_tpu.parallel.rpc import Reader, Writer
        d = _build_store(tmp_path)
        s = Storage(d)
        handlers = make_storage_handlers(s)
        w = Writer().u64(0).u64(0)          # tenant
        _write_filters(w, NAME_FILTER)
        w.i64(T0).i64(T0 + 100_000)
        w.u64(0)                            # trace flag
        w.u64(1)                            # budget: 1ms — expires at once
        faultinject.configure("storage:scan=delay:30")
        try:
            t0 = time.perf_counter()
            with pytest.raises(DeadlineExceededError):
                # streaming handlers build frames lazily; drain them
                list(handlers["searchColumns_v1"](Reader(w.payload())))
            took = time.perf_counter() - t0
            # one injected 30ms check interval + slack, NOT the full scan
            assert took < 2.0
        finally:
            faultinject.configure("")
            s.close()

    def test_wire_deadline_is_typed_and_never_marks_down(self):
        """A storage-side abort crosses the RPC boundary as a typed
        deadline error (vm:deadline marker -> RPCDeadlineError with
        waited=False) and the fan-out does NOT mark the node down."""
        from victoriametrics_tpu.parallel.cluster_api import (
            ClusterStorage, ClusterUnavailableError, StorageNodeClient)
        from victoriametrics_tpu.parallel.rpc import (HELLO_SELECT,
                                                      RPCDeadlineError,
                                                      RPCServer)

        def h_abort(r):
            raise DeadlineExceededError(
                "storage-side deadline exceeded: test")

        srv = RPCServer("127.0.0.1", 0, HELLO_SELECT,
                        {"searchColumns_v1": h_abort,
                         "search_v1": h_abort})
        srv.start()
        node = StorageNodeClient("127.0.0.1", srv.port, srv.port)
        try:
            with pytest.raises(RPCDeadlineError) as ei:
                node.search_columns(NAME_FILTER, T0, T0 + 1000)
            assert ei.value.waited is False
            assert "deadline" in str(ei.value)
            cs = ClusterStorage([node])
            with pytest.raises(ClusterUnavailableError):
                cs.search_columns(NAME_FILTER, T0, T0 + 1000)
            # the node did exactly what the budget asked: still healthy
            assert node.healthy, \
                "deadline abort wrongly marked the node down"
        finally:
            node.close()
            srv.stop()


# ---------------------------------------------------------------------------
# replica-aware partial accounting (satellite)
# ---------------------------------------------------------------------------

def test_rf_covered_failure_not_partial():
    """With RF=2 over two nodes, one failed node whose every hash range
    is covered by the surviving responder does NOT set partial;
    vm_partial_avoided_total ticks instead.  RF=1 keeps strict
    accounting."""
    from victoriametrics_tpu.parallel.cluster_api import (_PARTIAL_AVOIDED,
                                                          ClusterStorage)
    from victoriametrics_tpu.parallel.rpc import RPCError

    class FakeNode:
        def __init__(self, name, fail=False):
            self.name = name
            self.fail = fail
            self.down_until = 0.0
            self.marked = False

        @property
        def healthy(self):
            return True

        def mark_down(self, seconds=2.0):
            self.marked = True

        def label_names(self, *a, **k):
            if self.fail:
                raise RPCError("boom")
            return ["a", "b"]

    good, bad = FakeNode("n1"), FakeNode("n2", fail=True)
    cs = ClusterStorage([good, bad], replication_factor=2)
    cs.reset_partial()
    before = _PARTIAL_AVOIDED.get()
    assert cs.label_names() == ["a", "b"]
    assert cs.last_partial is False, \
        "RF-covered failure must not flag partial"
    assert _PARTIAL_AVOIDED.get() == before + 1
    assert bad.marked, "a genuinely failing node is still marked down"

    # RF=1: the same failure IS partial
    good2, bad2 = FakeNode("n1"), FakeNode("n2", fail=True)
    cs1 = ClusterStorage([good2, bad2], replication_factor=1)
    cs1.reset_partial()
    assert cs1.label_names() == ["a", "b"]
    assert cs1.last_partial is True


def test_rf_covered_delete_stays_partial():
    """Mutating fan-outs (deleteSeries) never claim replica coverage: a
    missed node means a missed tombstone."""
    from victoriametrics_tpu.parallel.cluster_api import ClusterStorage
    from victoriametrics_tpu.parallel.rpc import RPCError

    class FakeNode:
        def __init__(self, name, fail=False):
            self.name = name
            self.fail = fail
            self.down_until = 0.0

        @property
        def healthy(self):
            return True

        def mark_down(self, seconds=2.0):
            pass

        def delete_series(self, *a, **k):
            if self.fail:
                raise RPCError("boom")
            return 3

    cs = ClusterStorage([FakeNode("n1"), FakeNode("n2", fail=True)],
                        replication_factor=2)
    cs.reset_partial()
    assert cs.delete_series([]) == 3
    assert cs.last_partial is True
