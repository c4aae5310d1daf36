"""Cluster half of the cost-and-profile plane: vmselect-merged
CostTracker totals equal single-node totals (exact for samples/bytes),
old<->new RPC metadata-frame tolerance in both directions, the or-set
filter union through real search RPCs (golden corpus conformance on the
cluster path), and the profile_v1 fan-out with node tagging."""

import json
import os

import numpy as np
import pytest

# NOTE: no zstandard gate — ops/compress falls back to runtime-zlib
# framing when the package is absent (PR 4), and test_cluster.py runs
# the same RPC stack ungated

from victoriametrics_tpu.parallel.cluster_api import (ClusterStorage,
                                                      StorageNodeClient,
                                                      make_storage_handlers)
from victoriametrics_tpu.parallel.rpc import (HELLO_INSERT, HELLO_SELECT,
                                              RPCServer)
from victoriametrics_tpu.query.exec import exec_query
from victoriametrics_tpu.query.types import EvalConfig
from victoriametrics_tpu.storage.storage import Storage
from victoriametrics_tpu.utils import costacc

HERE = os.path.dirname(__file__)
T0 = 1_753_700_000_000
STEP = 60_000
# T0 is a literal 2025-07-28: a stated retention, so that no merge drops
# it whatever today's date is
RETENTION_MS = 100 * 365 * 86_400_000

def seed_rows():
    rows = []
    for i in range(12):
        lab = {"__name__": "orm", "idx": str(i),
               "dc": "east" if i % 2 else "west",
               "team": "a" if i % 3 else "b"}
        for j in range(40):
            rows.append((lab, T0 - 600_000 + j * 15_000, float(i + j)))
    return rows


class _Cluster:
    def __init__(self, tmp, n=2, **kw):
        self.stores, self.servers, nodes = [], [], []
        for k in range(n):
            st = Storage(str(tmp / f"n{k}"), retention_ms=RETENTION_MS)
            self.stores.append(st)
            h = make_storage_handlers(st)
            isrv = RPCServer("127.0.0.1", 0, HELLO_INSERT, h)
            ssrv = RPCServer("127.0.0.1", 0, HELLO_SELECT, h)
            isrv.start()
            ssrv.start()
            self.servers += [isrv, ssrv]
            nodes.append(StorageNodeClient("127.0.0.1", isrv.port,
                                           ssrv.port, name=f"n{k}"))
        self.cluster = ClusterStorage(nodes, **kw)

    def seed(self):
        self.cluster.add_rows(seed_rows())
        for st in self.stores:
            st.force_flush()

    def close(self):
        for srv in self.servers:
            srv.stop()
        self.cluster.close()
        for st in self.stores:
            st.close()


@pytest.fixture()
def cluster(tmp_path):
    c = _Cluster(tmp_path, n=2)
    c.seed()
    yield c.cluster
    c.close()


@pytest.fixture()
def single(tmp_path):
    s = Storage(str(tmp_path / "single"))
    s.add_rows(seed_rows())
    s.force_flush()
    yield s
    s.close()


def _kw(storage):
    return dict(start=T0 - 300_000, end=T0, step=STEP, storage=storage)


class TestClusterCostEquality:
    def test_fanout_merged_cost_equals_single_node(self, cluster, single):
        q = "sum(rate(orm[5m]))"
        ec_s = EvalConfig(**_kw(single))
        ec_c = EvalConfig(**_kw(cluster))
        rs = exec_query(ec_s, q)
        rc = exec_query(ec_c, q)
        assert len(rs) == len(rc) == 1
        np.testing.assert_allclose(rs[0].values, rc[0].values)
        cs, cc = ec_s.cost.summary(), ec_c.cost.summary()
        # exact equality for samples and bytes (RF=1: disjoint shards)
        assert cc["samplesScanned"] == cs["samplesScanned"] > 0
        assert cc["bytesRead"] == cs["bytesRead"] > 0
        # the storage-side shipped counts sum to the single-node scan
        assert cc["storageSamplesScanned"] == cs["samplesScanned"]
        # both nodes shipped a cost frame; no partial accounting
        assert ec_c.cost.remote_nodes == 2
        assert "costPartial" not in cc
        assert cc["rpcBytes"] > 0
        # remote fetch CPU buckets merged in under the same names
        assert any(k.startswith("fetch:")
                   for k in cc["cpuMsByPhase"])

    def test_old_server_new_client_degrades_to_partial(self, cluster,
                                                       monkeypatch):
        """New vmselect against old vmstorage (legacy meta dialect): the
        search works, cost accounting goes partial, no error."""
        monkeypatch.setenv("VM_RPC_LEGACY_META", "1")
        ec = EvalConfig(**_kw(cluster))
        rows = exec_query(ec, "sum(rate(orm[5m]))")
        assert len(rows) == 1
        s = ec.cost.summary()
        assert s["costPartial"] is True
        assert "storageSamplesScanned" not in s
        # the evaluator's own count still works
        assert s["samplesScanned"] > 0

    def test_old_client_new_server_ignores_extras(self, cluster):
        """Old vmselect against new vmstorage: emulate the pre-cost
        client read path (partial flag + optional trace only) at the
        marshal level and prove the response parses clean."""
        node = cluster.nodes[0]
        from victoriametrics_tpu.parallel.rpc import Writer
        from victoriametrics_tpu.parallel.cluster_api import (
            _write_filters, _write_tenant)
        w = _write_tenant(Writer(), (0, 0))
        _write_filters(w, [])
        w.i64(T0 - 900_000).i64(T0)
        # old clients send neither trace flag nor budget nor or_sets
        frames = list(node.select.call_stream("searchColumns_v1", w))
        meta = frames[-1]
        n = meta.u64()
        assert n == (1 << 32) - 1
        partial = bool(meta.u64())
        assert partial is False
        # legacy parse: first bytes field is "the trace"; an empty slot
        # fails json and is IGNORED by the old guard — exactly the old
        # client's behavior against this new frame
        assert meta.remaining
        b1 = meta.bytes_()
        with pytest.raises(ValueError):
            json.loads(b1)  # b"" — old client's except path
        # extras bytes follow; old clients never read them
        assert meta.remaining

    def test_tenant_usage_recorded_on_storage_nodes(self, cluster):
        """The vmstorage search handlers fold node-side cost into the
        per-tenant usage table (both node handlers run in-process
        here): one fan-out query leaves a non-zero 0:0 row WITHOUT any
        client-side record_usage call."""
        costacc.TENANT_USAGE.reset()
        ec = EvalConfig(**_kw(cluster))
        exec_query(ec, "orm")
        snap = costacc.TENANT_USAGE.snapshot()
        row = next(r for r in snap if r["tenant"] == "0:0")
        assert row["samplesScanned"] > 0
        assert row["queries"] >= 2  # one search RPC per node


CASES = json.load(open(os.path.join(HERE, "golden_or_corpus.json")))


class TestClusterOrUnion:
    @pytest.mark.parametrize("case", CASES, ids=lambda c: c["q"][:60])
    def test_golden_corpus_through_cluster(self, cluster, single, case):
        """{a="b" or c="d"} through a real vmselect fan-out returns
        identical rows to plain storage (acceptance: the golden corpus
        extended to the cluster path)."""
        got = exec_query(EvalConfig(**_kw(cluster)), case["q"])
        want = exec_query(EvalConfig(**_kw(single)), case["q"])
        gm = {r.metric_name.marshal(): np.asarray(r.values) for r in got}
        wm = {r.metric_name.marshal(): np.asarray(r.values) for r in want}
        assert set(gm) == set(wm) and len(gm) > 0, case["q"]
        for k in gm:
            np.testing.assert_array_equal(gm[k], wm[k], err_msg=case["q"])

    def test_union_against_legacy_node_falls_back_per_set(self, cluster,
                                                          single,
                                                          monkeypatch):
        """A union-less (old) storage node doesn't ack or_sets; the
        client re-issues one legacy call per set — same rows, no
        error."""
        monkeypatch.setenv("VM_RPC_LEGACY_META", "1")
        q = 'orm{dc="east" or team="b"}'
        got = exec_query(EvalConfig(**_kw(cluster)), q)
        want = exec_query(EvalConfig(**_kw(single)), q)
        assert len(got) == len(want) > 0
        for a, b in zip(got, want):
            assert a.metric_name.marshal() == b.metric_name.marshal()
            np.testing.assert_array_equal(a.values, b.values)

    def test_cluster_declares_union_support(self, cluster):
        assert cluster.supports_filter_union is True
        # the loud QueryError for union-less backends must be GONE on
        # the cluster path
        from victoriametrics_tpu.query.eval import filters_from_metric_expr
        from victoriametrics_tpu.query.metricsql import parse
        sets = filters_from_metric_expr(parse('{a="b" or c="d"}'), cluster)
        assert isinstance(sets[0], list) and len(sets) == 2


class TestProfileFanout:
    def test_profile_report_tags_nodes(self, cluster, monkeypatch):
        monkeypatch.setenv("VM_PROFILE_HZ", "50")
        from victoriametrics_tpu.utils import profiler
        try:
            profiler.PROFILER.take_sample()
            reps = cluster.profile_report()
            assert {r["node"] for r in reps} == {"n0", "n1"}
            for r in reps:
                assert r["stacks"], r["node"]
        finally:
            profiler.PROFILER.stop()

    def test_profile_report_reset_propagates_to_nodes(self, cluster,
                                                      monkeypatch):
        """?reset=1 must open a fresh window CLUSTER-wide: the reset
        flag rides profile_v1, so node aggregates clear too (an old
        node ignoring the trailing flag simply keeps its window)."""
        monkeypatch.setenv("VM_PROFILE_HZ", "50")
        from victoriametrics_tpu.utils import profiler
        # sample by hand only: the 50 Hz sampler that profile_v1 would
        # start (or an earlier test left running) can tick between the
        # nodes' reset and the assertion below
        profiler.PROFILER.stop()
        monkeypatch.setattr(profiler, "ensure_started", lambda: True)
        try:
            profiler.PROFILER.take_sample()
            reps = cluster.profile_report(reset=True)
            # both fake nodes share ONE in-process profiler: the first
            # node's reset may empty the second's snapshot, so only
            # assert that the read happened and the reset stuck
            assert any(r["stacks"] for r in reps)
            assert profiler.PROFILER.snapshot()["samples"] == 0
        finally:
            profiler.PROFILER.stop()

    def test_profile_v1_disabled_node_tolerated(self, cluster,
                                                monkeypatch):
        monkeypatch.setenv("VM_PROFILE_HZ", "0")
        assert cluster.profile_report() == []

    def test_vmselect_http_profile_merges_nodes(self, cluster,
                                                monkeypatch):
        monkeypatch.setenv("VM_PROFILE_HZ", "50")
        from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
        from victoriametrics_tpu.httpapi.server import HTTPServer
        from victoriametrics_tpu.utils import profiler
        from tests.apptest_helpers import Client
        api = PrometheusAPI(cluster)
        srv = HTTPServer("127.0.0.1", 0)
        api.register(srv, mode="select")
        srv.start()
        try:
            profiler.PROFILER.take_sample()
            client = Client(srv.port)
            code, body = client.get("/api/v1/status/profile",
                                    format="raw")
            assert code == 200
            snaps = json.loads(body)["data"]
            nodes = {s.get("node") for s in snaps}
            assert {"vmselect", "n0", "n1"} <= nodes
            # collapsed rendering carries the node prefixes
            code, body = client.get("/api/v1/status/profile")
            assert code == 200
            assert b"n0/" in body and b"n1/" in body
        finally:
            srv.stop()
            profiler.PROFILER.stop()
