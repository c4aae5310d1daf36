"""Self-observability tests: the central metrics registry (counters /
gauges / vmrange histograms / process_*), exposition round-trip through
the project's own Prometheus text parser, /metrics over HTTP, the
active/top-query status endpoints, pushmetrics label splicing + gzip,
tracer context-manager semantics, and cross-RPC trace propagation on a
2-node cluster."""

import gzip
import json

import pytest

from victoriametrics_tpu.ingest.parsers import parse_prometheus
from victoriametrics_tpu.utils import metrics as metricslib
from victoriametrics_tpu.utils import querytracer
from victoriametrics_tpu.utils.metrics import (MetricsRegistry,
                                               escape_label_value,
                                               format_name,
                                               splice_extra_labels)

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


def parse_exposition(text: str) -> dict:
    """name{sorted labels} -> float value, via the project's own parser."""
    out = {}
    for row in parse_prometheus(text, default_ts=T0):
        labels = dict(row.labels)
        name = labels.pop("__name__")
        key = (name, tuple(sorted(labels.items())))
        out[key] = row.value
    return out


def find_series(parsed: dict, name: str, **label_subset):
    return [(k, v) for k, v in parsed.items()
            if k[0] == name and
            all(dict(k[1]).get(lk) == lv
                for lk, lv in label_subset.items())]


class TestRegistry:
    def test_counter_and_float_counter(self):
        r = MetricsRegistry()
        c = r.counter("t_reqs_total")
        c.inc()
        c.inc(4)
        assert c.get() == 5
        assert r.counter("t_reqs_total") is c  # get-or-create
        fc = r.float_counter("t_secs_total")
        fc.inc(0.25)
        fc.inc(0.5)
        assert fc.get() == 0.75

    def test_gauge_set_and_callback(self):
        r = MetricsRegistry()
        g = r.gauge("t_g")
        g.set(3)
        g.inc()
        g.dec(2)
        assert g.get() == 2
        box = [7]
        cb = r.gauge("t_cb", callback=lambda: box[0])
        assert cb.get() == 7
        box[0] = 9
        assert cb.get() == 9

    def test_type_mismatch_rejected(self):
        r = MetricsRegistry()
        r.counter("t_x")
        with pytest.raises(ValueError):
            r.gauge("t_x")

    def test_invalid_name_rejected(self):
        r = MetricsRegistry()
        with pytest.raises(ValueError):
            r.counter('bad{unclosed="')

    def test_histogram_vmrange_buckets(self):
        from victoriametrics_tpu.utils.vmhistogram import vmrange_for
        r = MetricsRegistry()
        h = r.histogram('t_dur_seconds{path="/q"}')
        for v in (0.0015, 0.0015, 2.5):
            h.update(v)
        h.update(float("nan"))   # skipped
        h.update(-1.0)           # skipped
        assert h.get_count() == 3
        assert h.get_sum() == pytest.approx(2.503)
        # +Inf goes to the upper catch-all (reference behavior), not a crash
        h2 = r.histogram("t_inf_seconds")
        h2.update(float("inf"))
        assert h2.get_count() == 1
        from victoriametrics_tpu.utils.vmhistogram import (UPPER_RANGE,
                                                           vmrange_for)
        assert vmrange_for(float("inf")) == UPPER_RANGE
        text = r.write_prometheus(include_process=False)
        parsed = parse_exposition(text)
        b15 = find_series(parsed, "t_dur_seconds_bucket", path="/q",
                          vmrange=vmrange_for(0.0015))
        assert b15 and b15[0][1] == 2.0
        assert find_series(parsed, "t_dur_seconds_sum", path="/q")
        cnt = find_series(parsed, "t_dur_seconds_count", path="/q")
        assert cnt[0][1] == 3.0

    def test_write_prometheus_type_lines_and_roundtrip(self):
        r = MetricsRegistry()
        r.counter("t_a_total").inc(2)
        r.gauge("t_b").set(1.5)
        r.histogram("t_h").update(0.1)
        text = r.write_prometheus(extra={"t_extra_total": 7})
        assert "# TYPE t_a_total counter" in text
        assert "# TYPE t_b gauge" in text
        assert "# TYPE t_h histogram" in text
        assert "# TYPE t_extra_total counter" in text
        parsed = parse_exposition(text)
        assert parsed[("t_a_total", ())] == 2.0
        assert parsed[("t_b", ())] == 1.5
        assert parsed[("t_extra_total", ())] == 7.0
        # process metrics rendered and parseable
        assert find_series(parsed, "process_resident_memory_bytes")
        assert find_series(parsed, "process_num_threads")

    def test_label_escaping_roundtrip(self):
        r = MetricsRegistry()
        tricky = 'sp ace"quote\\slash\nnewline'
        r.counter(format_name("t_esc_total", {"v": tricky})).inc()
        parsed = parse_exposition(r.write_prometheus(
            include_process=False))
        rows = find_series(parsed, "t_esc_total")
        assert rows and dict(rows[0][0][1])["v"] == tricky
        assert escape_label_value('a"b') == 'a\\"b'


class TestSpliceExtraLabels:
    def test_plain_and_labeled(self):
        text = 'm1 42\nm2{x="y"} 7\n'
        out = splice_extra_labels(text, 'job="t"')
        assert 'm1{job="t"} 42' in out
        assert 'm2{job="t",x="y"} 7' in out

    def test_label_value_with_space_and_brace(self):
        # the old partition(" ") surgery split inside the label value
        text = 'm{x="a b}c"} 1\n'
        out = splice_extra_labels(text, 'job="t"')
        assert out == 'm{job="t",x="a b}c"} 1\n'

    def test_comments_kept(self):
        out = splice_extra_labels("# TYPE m counter\nm 1\n", 'a="b"')
        assert out.splitlines()[0] == "# TYPE m counter"


class TestPusherRender:
    def test_gzip_body_with_spliced_labels(self):
        from victoriametrics_tpu.utils.pushmetrics import MetricsPusher
        p = MetricsPusher([], lambda: 'm{x="a b"} 1\n',
                          extra_labels='job="t"')
        body = p._render()
        assert gzip.decompress(body) == b'm{job="t",x="a b"} 1\n'


class TestTracerContextManager:
    def test_closes_on_success_and_is_idempotent(self):
        t = querytracer.Tracer("root")
        with t.new_child("child") as c:
            c.donef("done %d", 3)
        d = t.to_dict()
        assert d["children"][0]["message"] == "child: done 3"

    def test_records_exception(self):
        t = querytracer.Tracer("root")
        with pytest.raises(ValueError):
            with t.new_child("will fail"):
                raise ValueError("boom")
        d = t.to_dict()
        assert "error: boom" in d["children"][0]["message"]

    def test_nop_tracer_contextmanager(self):
        with querytracer.NOP as n:
            assert not n.enabled
        querytracer.NOP.add_remote({"message": "x"})
        assert querytracer.NOP.to_dict() == {}

    def test_from_dict_graft(self):
        t = querytracer.Tracer("local")
        t.add_remote({"duration_msec": 5.0, "message": "remote",
                      "children": [{"duration_msec": 2.0,
                                    "message": "inner"}]})
        d = t.to_dict()
        assert d["children"][0]["message"] == "remote"
        assert d["children"][0]["children"][0]["message"] == "inner"
        assert d["children"][0]["duration_msec"] == 5.0


@pytest.fixture()
def app(tmp_path):
    """In-process vmsingle (same shape as test_vmsingle_http.app)."""
    from victoriametrics_tpu.apps.vmsingle import build, parse_flags

    from tests.apptest_helpers import Client
    args = parse_flags([f"-storageDataPath={tmp_path}/data",
                        "-httpListenAddr=127.0.0.1:0"])
    storage, srv, api = build(args)
    srv.start()
    yield Client(srv.port)
    srv.stop()
    storage.close()


def _ingest(app, name="sm", n=3):
    lines = "".join(f'{name}{{i="{k}"}} {k} {T0 + j * 15_000}\n'
                    for k in range(n) for j in range(20))
    code, _ = app.post("/api/v1/import/prometheus", lines.encode())
    assert code == 204


@needs_storage
class TestMetricsEndpoint:
    def test_exposition_parses_and_has_core_series(self, app):
        _ingest(app)
        # a cacheable range query twice: miss then hit on the rollup
        # result cache, plus a vm_request_duration_seconds sample
        for _ in range(2):
            res = app.query_range("sm", T0 / 1e3,
                                  (T0 + 300_000) / 1e3, 15)
            assert res["status"] == "success"
        code, body = app.get("/metrics")
        assert code == 200
        parsed = parse_exposition(body.decode())
        assert parsed, "empty /metrics"
        # per-path vmrange histogram of the request we just made
        buckets = find_series(parsed, "vm_request_duration_seconds_bucket",
                              path="/api/v1/query_range")
        assert buckets, "no vm_request_duration_seconds vmrange buckets"
        assert all("vmrange" in dict(k[1]) for k, _ in buckets)
        assert find_series(parsed, "vm_request_duration_seconds_count",
                           path="/api/v1/query_range")
        # cache hit/miss pair
        reqs = find_series(parsed, "vm_cache_requests_total",
                           type="promql/rollupResult")
        miss = find_series(parsed, "vm_cache_misses_total",
                           type="promql/rollupResult")
        assert reqs and miss
        assert reqs[0][1] >= miss[0][1]
        # process metrics
        rss = find_series(parsed, "process_resident_memory_bytes")
        assert rss and rss[0][1] > 0
        # legacy app-level counters still exposed
        assert find_series(parsed, "vm_rows_inserted_total")
        # per-path request counters
        assert find_series(parsed, "vm_http_requests_total",
                           path="/api/v1/query_range")

    def test_type_lines_present(self, app):
        code, body = app.get("/metrics")
        text = body.decode()
        assert "# TYPE vm_http_requests_total counter" in text
        assert "# TYPE process_resident_memory_bytes gauge" in text

    def test_active_and_top_queries(self, app):
        _ingest(app)
        app.query("sm", T0 / 1e3)
        app.query("sm", T0 / 1e3)
        code, body = app.get("/api/v1/status/top_queries")
        assert code == 200
        data = json.loads(body)
        top = [e for e in data["topByCount"] if e["query"] == "sm"]
        assert top and top[0]["count"] >= 2
        assert top[0]["sumDurationSeconds"] >= 0
        code, body = app.get("/api/v1/status/active_queries")
        assert code == 200
        assert json.loads(body)["status"] == "ok"


class TestQueryStatsRing:
    def test_ring_evicts_oldest(self):
        from victoriametrics_tpu.query.querystats import QueryStats
        qs = QueryStats(max_records=2)
        qs.record("a", 0, 0.1)
        qs.record("b", 0, 0.1)
        qs.record("c", 0, 0.1)
        got = {e["query"] for e in qs.top(10, "count")}
        assert got == {"b", "c"}  # "a" aged out of the ring

    def test_active_queries_gauge(self):
        from victoriametrics_tpu.query.querystats import ActiveQueries
        a = ActiveQueries()
        qid = a.register("q", 0, 0, 15)
        assert len(a) == 1
        snap = a.snapshot()
        assert snap[0]["query"] == "q" and "duration" in snap[0]
        a.unregister(qid)
        assert len(a) == 0


class TestTracePropagationProtocol:
    """Marshal-level halves of cross-RPC tracing — no sockets, no
    compression, so these run even without the zstandard dep."""

    class _FakeStorage:
        last_partial = False

        def search_series(self, filters, min_ts, max_ts, tenant=(0, 0)):
            return []

        def reset_partial(self):
            pass

    def _search_frames(self, trace_flag: int):
        from victoriametrics_tpu.parallel.cluster_api import \
            make_storage_handlers
        from victoriametrics_tpu.parallel.rpc import Reader, Writer
        handlers = make_storage_handlers(self._FakeStorage())
        w = Writer().u64(0).u64(0)   # tenant
        w.u64(0)                     # no filters
        w.i64(T0).i64(T0 + 1000)
        w.u64(trace_flag)
        return list(handlers["search_v1"](Reader(w.payload())))

    def test_meta_frame_carries_storage_span_tree(self):
        from victoriametrics_tpu.parallel.rpc import Reader
        frames = self._search_frames(trace_flag=1)
        meta = Reader(frames[-1].payload())
        assert meta.u64() == (1 << 32) - 1
        assert meta.u64() == 0  # not partial
        tree = json.loads(meta.bytes_())
        assert tree["message"].startswith("vmstorage search_v1")
        assert tree["children"][0]["message"].startswith("search_series")

    def test_no_trace_flag_means_empty_trace_slot(self):
        """Without the trace flag the meta frame carries an EMPTY trace
        slot followed by the extras dict (cost frame + union ack) — an
        old client's json parse of b"" fails into its existing
        malformed-trace guard, so the old-client behavior is
        unchanged."""
        from victoriametrics_tpu.parallel.rpc import Reader
        frames = self._search_frames(trace_flag=0)
        meta = Reader(frames[-1].payload())
        meta.u64(), meta.u64()
        assert meta.bytes_() == b""  # the empty trace slot
        extras = json.loads(meta.bytes_())
        assert extras["filterUnion"] is True
        assert "samples" in extras["cost"]
        assert meta.remaining == 0

    def test_old_client_without_flag_still_served(self):
        """A request WITHOUT the trailing trace flag (pre-extension
        client) is parsed identically; the response's trace slot stays
        empty."""
        from victoriametrics_tpu.parallel.cluster_api import \
            make_storage_handlers
        from victoriametrics_tpu.parallel.rpc import Reader, Writer
        handlers = make_storage_handlers(self._FakeStorage())
        w = Writer().u64(0).u64(0)
        w.u64(0)
        w.i64(T0).i64(T0 + 1000)
        frames = list(handlers["search_v1"](Reader(w.payload())))
        meta = Reader(frames[-1].payload())
        meta.u64(), meta.u64()
        assert meta.bytes_() == b""
        assert "filterUnion" in json.loads(meta.bytes_())

    def test_client_grafts_remote_tree(self):
        from victoriametrics_tpu.parallel.cluster_api import \
            StorageNodeClient
        from victoriametrics_tpu.parallel.rpc import Reader, Writer
        remote = {"duration_msec": 4.2, "message": "vmstorage search_v1",
                  "children": [{"duration_msec": 1.0,
                                "message": "search_series: 5 series"}]}
        # OLD-server frame shape: [partial][trace], no extras — the new
        # client must parse it and answer extras=None (degraded cost)
        meta = Writer().u64(1)  # partial flag (count already consumed)
        meta.bytes_(json.dumps(remote).encode())
        qt = querytracer.Tracer("rpc node n1")
        partial, extras = StorageNodeClient._read_meta(
            Reader(meta.payload()), qt)
        assert partial is True
        assert extras is None
        d = qt.to_dict()
        assert d["children"][0]["message"] == "vmstorage search_v1"
        assert d["children"][0]["children"][0]["message"] == \
            "search_series: 5 series"


@needs_storage
class TestClusterObservability:
    def test_storage_node_span_in_query_trace(self, tmp_path):
        """A trace=1 query against a 2-node cluster returns a trace tree
        containing spans generated ON the storage nodes (serialized over
        the search RPC and grafted into the vmselect trace), and the
        select node's /metrics shows RPC client durations."""
        from tests.apptest_helpers import Client
        from tests.test_cluster import StorageNode
        from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
        from victoriametrics_tpu.httpapi.server import HTTPServer
        from victoriametrics_tpu.parallel.cluster_api import ClusterStorage

        nodes = [StorageNode(tmp_path / f"n{i}") for i in range(2)]
        cluster = ClusterStorage([n.client() for n in nodes],
                                 replication_factor=1)
        try:
            rows = []
            for i in range(8):
                for j in range(30):
                    rows.append(({"__name__": "cm", "idx": str(i)},
                                 T0 + j * 15_000, float(i * 100 + j)))
            cluster.add_rows(rows)
            srv = HTTPServer("127.0.0.1", 0)
            PrometheusAPI(cluster).register(srv, mode="select")
            srv.start()
            try:
                c = Client(srv.port)
                code, body = c.get(
                    "/api/v1/query_range", query="cm",
                    start=str(T0 / 1e3), end=str((T0 + 450_000) / 1e3),
                    step="15", trace="1", nocache="1")
                assert code == 200, body
                res = json.loads(body)
                assert res["data"]["result"], "no data from cluster"
                trace = res.get("trace")
                assert trace, "trace=1 returned no trace tree"

                def messages(d):
                    yield d.get("message", "")
                    for ch in d.get("children", ()):
                        yield from messages(ch)

                msgs = list(messages(trace))
                storage_spans = [m for m in msgs
                                 if m.startswith("vmstorage ")]
                assert storage_spans, \
                    f"no storage-node span in trace: {msgs}"
                # both nodes answered -> at least one rpc span per node
                rpc_spans = [m for m in msgs if "node 127.0.0.1" in m]
                assert len(rpc_spans) >= 2, msgs
                # durations survive serialization
                assert all(d.get("duration_msec", 0) >= 0
                           for d in [trace])

                # select-side /metrics: RPC client duration series
                code, body = c.get("/metrics")
                parsed = parse_exposition(body.decode())
                assert find_series(
                    parsed, "vm_rpc_client_call_duration_seconds_count")
                assert find_series(parsed, "vm_rpc_client_calls_total")
            finally:
                srv.stop()
        finally:
            cluster.close()
            for n in nodes:
                n.stop()

    def test_rpc_server_metrics_counted(self, tmp_path):
        """The storage node side counts served RPC calls."""
        from victoriametrics_tpu.storage.tag_filters import \
            filters_from_dict
        from tests.test_cluster import StorageNode

        before = metricslib.REGISTRY.counter(
            'vm_rpc_server_calls_total{method="search_v1"}').get()
        node = StorageNode(tmp_path / "n")
        try:
            client = node.client()
            out, partial = client.search_series(
                filters_from_dict({"__name__": "cm"}), T0, T0 + 1000)
            assert out == [] and partial is False
        finally:
            node.stop()
        after = metricslib.REGISTRY.counter(
            'vm_rpc_server_calls_total{method="search_v1"}').get()
        assert after >= before + 1
