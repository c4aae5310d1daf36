"""Elastic scale-out serving (ROADMAP item 3): reroute-aware
ring-filtered reads, live resharding over the migrateParts_v1 family,
and multilevel vmselect fan-out — the in-process tier-1 half (the
subprocess chaos scenarios live in test_chaos_cluster.py).

Everything here runs real RPC over loopback TCP against real Storage
engines, just inside one process for speed.
"""

import os
import tempfile

import numpy as np
import pytest

from victoriametrics_tpu.parallel import ringfilter
from victoriametrics_tpu.parallel.cluster_api import (
    ClusterStorage, StorageNodeClient, make_storage_handlers,
    parse_node_spec, start_native_server)
from victoriametrics_tpu.parallel.rpc import (HELLO_INSERT, HELLO_SELECT,
                                              RPCError, RPCServer)
from victoriametrics_tpu.storage.storage import Storage
from victoriametrics_tpu.storage.tag_filters import TagFilter
from victoriametrics_tpu.utils import metrics as metricslib

zstd_missing = False
try:  # the RPC frame layer needs a zstd codec (python pkg or dlopen)
    from victoriametrics_tpu.ops import compress as _c
    _c.compress(b"probe")
except Exception:  # pragma: no cover - env without any zstd
    zstd_missing = True

pytestmark = pytest.mark.skipif(zstd_missing,
                                reason="no zstd codec available")

T0 = 1_753_700_000_000
# T0 is a literal 2025-07-28: a stated retention, so that no merge drops
# it whatever today's date is
RETENTION_MS = 100 * 365 * 86_400_000
_REROUTES = metricslib.REGISTRY.counter("vm_reroute_reads_total")
_MIGRATED = metricslib.REGISTRY.counter("vm_parts_migrated_total")
_MOVED_BYTES = metricslib.REGISTRY.counter("vm_rebalance_moved_bytes_total")


class Node:
    """One in-process 'vmstorage': Storage + both RPC planes."""

    def __init__(self, tag: str):
        self.store = Storage(tempfile.mkdtemp(prefix=f"elastic-{tag}-"),
                             retention_ms=RETENTION_MS)
        handlers = make_storage_handlers(self.store)
        self.ins = RPCServer("127.0.0.1", 0, HELLO_INSERT, handlers)
        self.sel = RPCServer("127.0.0.1", 0, HELLO_SELECT, handlers)
        self.ins.start()
        self.sel.start()

    def client(self) -> StorageNodeClient:
        return StorageNodeClient("127.0.0.1", self.ins.port, self.sel.port)

    @property
    def spec(self) -> str:
        return f"127.0.0.1:{self.ins.port}:{self.sel.port}"

    def close(self):
        self.ins.stop()
        self.sel.stop()
        self.store.close()


@pytest.fixture(autouse=True)
def _fast_migration_grace(monkeypatch):
    """No concurrent readers in these tests: shrink the source-copy
    grace window (VM_MIGRATE_GRACE_MS) so drains don't sleep 1.5s."""
    monkeypatch.setenv("VM_MIGRATE_GRACE_MS", "50")


@pytest.fixture()
def nodes2():
    ns = [Node("a"), Node("b")]
    yield ns
    for n in ns:
        n.close()


@pytest.fixture()
def nodes3():
    ns = [Node("a"), Node("b"), Node("c")]
    yield ns
    for n in ns:
        n.close()


def seed(cluster, name="em", n=60, k=3):
    rows = [({"__name__": name, "series": str(i)},
             T0 + j * 15_000, float(i * 100 + j))
            for i in range(n) for j in range(k)]
    cluster.add_rows(rows)
    return rows


def fetch(cluster, name="em", lo=T0, hi=T0 + 60_000):
    return cluster.search_columns([TagFilter(b"", name.encode())], lo, hi)


def assert_same(a, b):
    assert a.raw_names == b.raw_names
    assert np.array_equal(a.counts, b.counts)
    assert np.array_equal(a.ts, b.ts)
    assert np.array_equal(a.vals, b.vals)


# ---------------------------------------------------------------------------
# ring-ownership read filtering
# ---------------------------------------------------------------------------

class TestRingFilteredReads:
    def test_ring_on_equals_ring_off(self, nodes2):
        """The oracle: ring-filtered reads are bit-equal to the full
        fan-out (VM_RING_FILTER=0), healthy and with rf=1/rf=2."""
        for rf in (1, 2):
            cluster = ClusterStorage([n.client() for n in nodes2],
                                     replication_factor=rf)
            seed(cluster, name=f"rr{rf}")
            on = fetch(cluster, f"rr{rf}")
            os.environ["VM_RING_FILTER"] = "0"
            try:
                off = fetch(cluster, f"rr{rf}")
            finally:
                del os.environ["VM_RING_FILTER"]
            assert on.n_series == 60
            assert_same(on, off)
            cluster.close()

    def test_rf2_suppresses_duplicate_replica_rows(self, nodes2):
        """With RF=2 every series lives on both nodes; ring filtering
        makes each node serve only its primary share, so the bytes
        crossing the wire drop ~2x (the read-amplification win)."""
        cluster = ClusterStorage([n.client() for n in nodes2],
                                 replication_factor=2)
        seed(cluster)
        ring0 = ringfilter.get_ring(cluster.node_names(), 2, 0,
                                    frozenset())
        ring1 = ringfilter.get_ring(cluster.node_names(), 2, 1,
                                    frozenset())
        f = [TagFilter(b"", b"em")]
        n0 = cluster.nodes[0].search_columns(f, T0, T0 + 60_000,
                                             ring=ring0)
        n1 = cluster.nodes[1].search_columns(f, T0, T0 + 60_000,
                                             ring=ring1)
        served = len(n0[0]) + len(n1[0])
        assert served == 60, f"primary shares must partition: {served}"
        # unfiltered, both nodes return everything (2x amplification)
        u0 = cluster.nodes[0].search_columns(f, T0, T0 + 60_000)
        u1 = cluster.nodes[1].search_columns(f, T0, T0 + 60_000)
        assert len(u0[0]) + len(u1[0]) == 120
        cluster.close()

    def test_down_node_rerouted_complete(self, nodes2):
        """ISSUE acceptance: a down shard is served via explicit
        reroute — complete (not partial) results, with
        vm_reroute_reads_total ticking on the vmselect side."""
        cluster = ClusterStorage([n.client() for n in nodes2],
                                 replication_factor=2)
        seed(cluster)
        before = fetch(cluster)
        r0 = _REROUTES.get()
        cluster.nodes[0].mark_down(30.0)
        cluster.reset_partial()
        after = fetch(cluster)
        assert_same(before, after)
        assert not cluster.last_partial
        assert _REROUTES.get() > r0
        cluster.nodes[0].down_until = 0.0
        cluster.close()

    def test_unmarked_failure_goes_partial_not_silent(self, nodes2):
        """A fan-out failure that never flips node.healthy
        (waited=False: pre-exhausted budget, local pool capacity) must
        not be claimed replica-covered under ring filtering — the
        survivors suppressed the failed node's shares, so the result
        goes HONESTLY partial after the one bounded re-fan."""
        from victoriametrics_tpu.parallel.rpc import RPCDeadlineError
        cluster = ClusterStorage([n.client() for n in nodes2],
                                 replication_factor=2)
        seed(cluster, name="uf")
        orig = cluster.nodes[0].search_columns

        def boom(*a, **k):
            err = RPCDeadlineError("budget pre-exhausted before I/O")
            err.waited = False
            raise err

        cluster.nodes[0].search_columns = boom
        try:
            cluster.reset_partial()
            cols = fetch(cluster, "uf")
            assert cluster.last_partial, \
                "suppressed shares silently claimed complete"
            assert 0 < cols.n_series < 60
            # waited=False never poisons the node's health
            assert cluster.nodes[0].healthy
        finally:
            cluster.nodes[0].search_columns = orig
        cluster.reset_partial()
        assert fetch(cluster, "uf").n_series == 60
        assert not cluster.last_partial
        cluster.close()

    def test_write_reroute_marks_exempt(self, nodes2):
        """rf=1: rows rerouted while their owner was down are marked
        ring-exempt on the node that took them — after the owner comes
        back, ring-filtered reads still serve every row."""
        cluster = ClusterStorage([n.client() for n in nodes2])
        seed(cluster, name="wr", n=40)
        # kill node 0's servers so writes to it fail over to node 1
        # (stop() only closes the LISTENER; drop the kept-alive client
        # connection too so the reconnect actually fails)
        nodes2[0].ins.stop()
        nodes2[0].sel.stop()
        cluster.nodes[0].insert.close()
        rows = [({"__name__": "wr", "series": str(i)},
                 T0 + 90_000, float(i)) for i in range(40)]
        cluster.add_rows(rows)
        # owner back up (same Storage, fresh servers on fresh ports)
        n0 = nodes2[0]
        handlers = make_storage_handlers(n0.store)
        n0.ins = RPCServer("127.0.0.1", 0, HELLO_INSERT, handlers)
        n0.sel = RPCServer("127.0.0.1", 0, HELLO_SELECT, handlers)
        n0.ins.start()
        n0.sel.start()
        old_name = cluster.nodes[0].name
        revived = StorageNodeClient("127.0.0.1", n0.ins.port, n0.sel.port,
                                    name=old_name)
        cluster._set_nodes([revived, cluster.nodes[1]])
        cols = fetch(cluster, "wr", hi=T0 + 120_000)
        assert cols.n_series == 40
        # every rerouted sample present despite the healthy owner
        assert int(cols.counts.sum()) == 40 * 4
        # and the exemption is durable state on the taker
        assert len(nodes2[1].store.ring_exempt_names) > 0
        cluster.close()


# ---------------------------------------------------------------------------
# live resharding: migrate / drain / join+rebalance
# ---------------------------------------------------------------------------

class TestLiveResharding:
    def test_export_adopt_roundtrip_direct(self):
        """Storage-level: an exported part adopts byte-exactly on a
        fresh node, foreign metric_ids resolve, and narrow (per-day
        indexed) searches see the adopted data."""
        a = Storage(tempfile.mkdtemp(prefix="mig-a-"))
        b = Storage(tempfile.mkdtemp(prefix="mig-b-"))
        try:
            rows = [({"__name__": "mg", "series": str(i)},
                     T0 + j * 15_000, float(i + j))
                    for i in range(25) for j in range(3)]
            a.add_rows(rows)
            a.force_flush()
            inv = a.list_file_parts()
            assert inv and all(r["rows"] > 0 for r in inv)
            want = a.search_columns([TagFilter(b"", b"mg")], T0,
                                    T0 + 60_000)
            for row in inv:
                files, entries, meta = a.export_part(row["partition"],
                                                     row["part"])
                assert entries, "registrations must ship with the part"
                got_rows, got_bytes = b.adopt_part(
                    row["partition"], files, entries,
                    meta["min_ts"], meta["max_ts"])
                assert got_rows == row["rows"]
            got = b.search_columns([TagFilter(b"", b"mg")], T0,
                                   T0 + 60_000)
            assert got.raw_names == want.raw_names
            assert np.array_equal(got.vals, want.vals)
            # metric names resolve through the adopted registrations
            assert got.metric_names[0].metric_group == b"mg"
            # the generator skipped past every adopted id (a later
            # local series can never collide with a migrated one)
            assert b._mid_gen.next_id() > max(
                int(m) for m in got.metric_ids)
        finally:
            a.close()
            b.close()

    def test_adopt_rejects_torn_transfer(self):
        """The PR-10 integrity gate holds for migration: a corrupted
        byte in a transferred file rejects the adoption."""
        from victoriametrics_tpu.utils import fs as fslib
        a = Storage(tempfile.mkdtemp(prefix="torn-a-"))
        b = Storage(tempfile.mkdtemp(prefix="torn-b-"))
        try:
            # varying multi-sample series so timestamps.bin/values.bin
            # hold real payloads (single-sample const blocks encode to
            # zero bytes and there would be nothing to corrupt)
            a.add_rows([({"__name__": "tn", "series": str(i)},
                         T0 + j * 15_000, float(i * 7 + j * 3 + 1))
                        for i in range(20) for j in range(5)])
            a.force_flush()
            row = a.list_file_parts()[0]
            files, entries, meta = a.export_part(row["partition"],
                                                 row["part"])
            victim = next(n for n, d in files
                          if n.endswith(".bin") and d)
            files = [(n, (bytes([d[0] ^ 0xFF]) + d[1:]
                          if n == victim else d))
                     for n, d in files]
            with pytest.raises(fslib.IntegrityError):
                b.adopt_part(row["partition"], files, entries,
                             meta["min_ts"], meta["max_ts"])
            assert b.list_file_parts() == []
            # and a wire-supplied partition name cannot escape the
            # data directory (strict YYYY_MM or rejected)
            with pytest.raises(ValueError):
                b.adopt_part("../a_bc", files, entries)
            with pytest.raises(ValueError):
                b.adopt_part("2026_xx", files, entries)
        finally:
            a.close()
            b.close()

    def test_drain_node_byte_exact(self, nodes3):
        """DRAIN: all parts migrate off, the ring shrinks, reads stay
        byte-exact, and vm_parts_migrated_total accounts the moves."""
        cluster = ClusterStorage([n.client() for n in nodes3])
        seed(cluster, n=90)
        for n in nodes3:
            n.store.force_flush()
        want = fetch(cluster)
        assert want.n_series == 90
        victim = cluster.node_names()[0]
        m0, b0 = _MIGRATED.get(), _MOVED_BYTES.get()
        stat = cluster.drain_node(victim)
        assert stat["removed"] and stat["parts"] >= 1
        assert _MIGRATED.get() > m0 and _MOVED_BYTES.get() > b0
        assert len(cluster.nodes) == 2
        got = fetch(cluster)
        assert_same(want, got)
        # the drained node's engine is empty of finalized parts
        assert nodes3[0].store.list_file_parts() == []
        cluster.close()

    def test_drain_includes_unflushed_acked_writes(self, nodes3):
        """Zero dropped acked writes: rows acked but NOT yet flushed on
        the victim are flushed by the drain itself and survive."""
        cluster = ClusterStorage([n.client() for n in nodes3])
        seed(cluster, name="uf", n=50)       # acked, still in memory
        want = fetch(cluster, "uf")
        victim = cluster.node_names()[2]
        cluster.drain_node(victim)
        got = fetch(cluster, "uf")
        assert_same(want, got)
        cluster.close()

    def test_join_and_rebalance(self, nodes2):
        """JOIN: a fresh node enters the ring without a restart; new
        writes shard onto it; rebalance_to moves a byte share of
        existing parts; reads stay byte-exact throughout."""
        joiner = Node("j")
        try:
            cluster = ClusterStorage([n.client() for n in nodes2])
            # several flush batches -> several movable parts
            for b in range(3):
                rows = [({"__name__": "jn", "series": str(i)},
                         T0 + (3 * b + j) * 15_000, float(i + b))
                        for i in range(40) for j in range(3)]
                cluster.add_rows(rows)
                for n in nodes2:
                    n.store.force_flush()
            want = fetch(cluster, "jn", hi=T0 + 10 * 15_000)
            cluster.add_node(joiner.spec)
            assert len(cluster.nodes) == 3
            # new writes reach the joiner
            rows = [({"__name__": "jn2", "series": str(i)}, T0, float(i))
                    for i in range(60)]
            cluster.add_rows(rows)
            assert joiner.store.rows_added > 0
            stat = cluster.rebalance_to(joiner.client().name)
            assert stat["parts"] >= 1, stat
            assert joiner.store.list_file_parts() != []
            got = fetch(cluster, "jn", hi=T0 + 10 * 15_000)
            assert_same(want, got)
            cluster.close()
        finally:
            joiner.close()

    def test_drain_rejects_when_no_targets(self, nodes2):
        cluster = ClusterStorage([n.client() for n in nodes2])
        seed(cluster, name="nt", n=10)
        cluster.drain_node(cluster.node_names()[0])
        last = cluster.node_names()[0]
        with pytest.raises((RPCError, ValueError)):
            cluster.drain_node(last)
        # a FAILED drain must not leave the node write-excluded forever
        assert last not in cluster._draining
        cluster.add_rows([({"__name__": "nt2", "series": "0"},
                           T0, 1.0)])
        assert fetch(cluster, "nt2").n_series == 1
        cluster.close()


# ---------------------------------------------------------------------------
# multilevel vmselect
# ---------------------------------------------------------------------------

class TestMultilevel:
    def test_parse_node_spec_forms(self):
        assert parse_node_spec("127.0.0.1:8400:8401") == \
            ("127.0.0.1", 8400, 8401)
        assert parse_node_spec("10.0.0.5:9000") == ("10.0.0.5", 9000, 9000)
        with pytest.raises(ValueError):
            parse_node_spec("nonsense")

    def test_tree_rows_byte_identical_to_flat(self, nodes2):
        """ISSUE acceptance: vmselect -> vmselect -> 2x vmstorage rows
        are byte-identical to the flat fan-out, and partials/traces
        propagate through the tree."""
        from victoriametrics_tpu.utils import querytracer
        flat = ClusterStorage([n.client() for n in nodes2])
        seed(flat, name="ml", n=80)
        mid = ClusterStorage([n.client() for n in nodes2])
        mid_srv = start_native_server("127.0.0.1:0", HELLO_SELECT, mid)
        try:
            top = ClusterStorage([StorageNodeClient(
                "127.0.0.1", mid_srv.port, mid_srv.port)])
            want = fetch(flat, "ml")
            got = fetch(top, "ml")
            assert want.n_series == 80
            assert_same(want, got)
            # cost propagation: the top-level query's tracker sees the
            # tree's node-side scan counts through the mid-level merge
            # (they land in storage_samples by design — .samples is the
            # evaluator's own merged-result count)
            from victoriametrics_tpu.utils import costacc
            tr = costacc.CostTracker()
            prev = costacc.set_current(tr)
            try:
                fetch(top, "ml")
            finally:
                costacc.set_current(prev)
            assert tr.storage_samples > 0
            assert tr.remote_nodes >= 1
            # trace composes: per-node rpc spans nested two levels deep
            qt = querytracer.new(True, "top")
            top.search_columns([TagFilter(b"", b"ml")], T0, T0 + 60_000,
                               tracer=qt)
            qt.donef("done")
            import json as _json
            assert _json.dumps(qt.to_dict()).count(
                "searchColumns_v1") >= 3
            # partial propagates up the tree
            mid.nodes[0].mark_down(30.0)
            top.reset_partial()
            part = fetch(top, "ml")
            assert top.last_partial and 0 < part.n_series < 80
            mid.nodes[0].down_until = 0.0
            top.close()
        finally:
            mid_srv.stop()
        flat.close()
