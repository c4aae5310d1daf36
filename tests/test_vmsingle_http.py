"""End-to-end HTTP API tests (apptest/tests analog): every ingest protocol
in, Prometheus API out. Uses an in-process server for speed plus one real
subprocess test."""

import json
import math
import time

import numpy as np
import pytest

from victoriametrics_tpu.ingest import remote_write
from tests.apptest_helpers import Client, VmSingleProc

T0 = 1_753_700_000_000


@pytest.fixture()
def app(tmp_path):
    """In-process vmsingle."""
    from victoriametrics_tpu.apps.vmsingle import build, parse_flags
    args = parse_flags([f"-storageDataPath={tmp_path}/data",
                        "-httpListenAddr=127.0.0.1:0"])
    storage, srv, api = build(args)
    srv.start()
    client = Client(srv.port)
    client.api = api  # for tests that evaluate beside the server
    yield client
    srv.stop()
    storage.close()


def ingest_remote_write(app, n_series=4, n_samples=20):
    series = []
    for i in range(n_series):
        labels = [("__name__", "rw_metric"), ("idx", str(i))]
        samples = [(T0 + j * 15_000, float(i * 100 + j))
                   for j in range(n_samples)]
        series.append((labels, samples))
    body = remote_write.build_write_request(series)
    code, resp = app.post("/api/v1/write", body,
                          headers={"Content-Encoding": "snappy"})
    assert code == 204, resp


class TestRemoteWrite:
    def test_write_then_query_range(self, app):
        ingest_remote_write(app)
        res = app.query_range("rw_metric", T0 / 1e3, (T0 + 300_000) / 1e3, 15)
        assert res["status"] == "success"
        assert len(res["data"]["result"]) == 4
        s0 = [r for r in res["data"]["result"]
              if r["metric"]["idx"] == "0"][0]
        assert s0["values"][0][1] == "0"
        assert s0["metric"]["__name__"] == "rw_metric"

    def test_zstd_encoding(self, app):
        body = remote_write.build_write_request(
            [([("__name__", "zm")], [(T0, 5.0)])], compress="zstd")
        code, _ = app.post("/api/v1/write", body,
                           headers={"Content-Encoding": "zstd"})
        assert code == 204
        res = app.query("zm", T0 / 1e3 + 10)
        assert res["data"]["result"][0]["value"][1] == "5"

    def test_instant_query_and_rate(self, app):
        ingest_remote_write(app)
        res = app.query("sum(rate(rw_metric[1m]))", (T0 + 290_000) / 1e3)
        v = float(res["data"]["result"][0]["value"][1])
        # each series grows 1 per 15s -> rate 1/15 x 4 series
        assert abs(v - 4 / 15) < 1e-9


class TestOtherProtocols:
    def test_influx_line(self, app):
        line = f"cpu,host=h1 usage_user=42.5,usage_system=7 {T0 * 1_000_000}"
        code, _ = app.post("/write", line.encode())
        assert code == 204
        res = app.query("cpu_usage_user", T0 / 1e3 + 10)
        r = res["data"]["result"][0]
        assert r["metric"] == {"__name__": "cpu_usage_user", "host": "h1"}
        assert r["value"][1] == "42.5"

    def test_jsonl_import_export_roundtrip(self, app):
        line = json.dumps({"metric": {"__name__": "jm", "a": "b"},
                           "values": [1.5, 2.5],
                           "timestamps": [T0, T0 + 60_000]})
        code, _ = app.post("/api/v1/import", line.encode())
        assert code == 204
        code, body = app.get("/api/v1/export", **{"match[]": "jm"})
        assert code == 200
        out = json.loads(body.splitlines()[0])
        assert out["metric"] == {"__name__": "jm", "a": "b"}
        assert out["values"] == [1.5, 2.5]
        assert out["timestamps"] == [T0, T0 + 60_000]

    def test_prometheus_text_import(self, app):
        text = f'pm{{x="1"}} 3.5 {T0}\npm{{x="2"}} 4.5 {T0}\n'
        code, _ = app.post("/api/v1/import/prometheus", text.encode())
        assert code == 204
        res = app.query("sum(pm)", T0 / 1e3 + 10)
        assert res["data"]["result"][0]["value"][1] == "8"

    def test_csv_import(self, app):
        csv = "h1,42.5,1753700000\nh2,7.5,1753700000\n"
        code, _ = app.post("/api/v1/import/csv", csv.encode(),
                           format="1:label:host,2:metric:temp,3:time:unix_s")
        assert code == 204
        res = app.query("temp", T0 / 1e3 + 10)
        assert len(res["data"]["result"]) == 2

    def test_graphite(self, app):
        line = f"foo.bar.baz;dc=east 10.5 {T0 // 1000}"
        code, _ = app.post("/graphite", line.encode())
        assert code == 204
        res = app.query('{__name__="foo.bar.baz"}', T0 / 1e3 + 10)
        assert res["data"]["result"][0]["metric"]["dc"] == "east"

    def test_opentsdb_http(self, app):
        body = json.dumps([{"metric": "ot.m", "timestamp": T0 // 1000,
                            "value": 9.5, "tags": {"t": "x"}}])
        code, _ = app.post("/api/put", body.encode())
        assert code == 204
        res = app.query('{__name__="ot.m"}', T0 / 1e3 + 10)
        assert res["data"]["result"][0]["value"][1] == "9.5"

    def test_datadog_v1(self, app):
        body = json.dumps({"series": [{
            "metric": "dd.metric", "points": [[T0 // 1000, 3.25]],
            "host": "h9", "tags": ["env:prod"]}]})
        code, _ = app.post("/datadog/api/v1/series", body.encode())
        assert code == 202
        res = app.query("dd_metric", T0 / 1e3 + 10)
        m = res["data"]["result"][0]["metric"]
        assert m["host"] == "h9" and m["env"] == "prod"

    def test_datadog_v2(self, app):
        body = json.dumps({"series": [{
            "metric": "dd2.m", "points": [{"timestamp": T0 // 1000,
                                           "value": 1.5}],
            "resources": [{"type": "host", "name": "h3"}]}]})
        code, _ = app.post("/datadog/api/v2/series", body.encode())
        assert code == 202
        res = app.query("dd2_m", T0 / 1e3 + 10)
        assert res["data"]["result"][0]["metric"]["host"] == "h3"

    def test_newrelic(self, app):
        body = json.dumps([{"Events": [{
            "eventType": "SystemSample", "timestamp": T0 // 1000,
            "cpuPercent": 12.5, "hostname": "nr1"}]}])
        code, _ = app.post("/newrelic/infra/v2/metrics/events/bulk",
                           body.encode())
        assert code == 202
        res = app.query("system_sample_cpu_percent", T0 / 1e3 + 10)
        assert res["data"]["result"][0]["metric"]["hostname"] == "nr1"


class TestMetadataAPIs:
    def test_series_labels_values(self, app):
        ingest_remote_write(app)
        code, body = app.get("/api/v1/series", **{
            "match[]": "rw_metric", "start": T0 / 1e3,
            "end": (T0 + 600_000) / 1e3})
        data = json.loads(body)["data"]
        assert len(data) == 4
        code, body = app.get("/api/v1/labels", start=T0 / 1e3,
                             end=(T0 + 600_000) / 1e3)
        assert "idx" in json.loads(body)["data"]
        code, body = app.get("/api/v1/label/idx/values", start=T0 / 1e3,
                             end=(T0 + 600_000) / 1e3)
        assert json.loads(body)["data"] == ["0", "1", "2", "3"]

    def test_status_tsdb(self, app):
        ingest_remote_write(app)
        code, body = app.get("/api/v1/status/tsdb")
        data = json.loads(body)["data"]
        assert data["totalSeries"] == 4
        assert data["labelValueCountByLabelName"]

    def test_status_tsdb_drilldown(self, app):
        ingest_remote_write(app)
        app.post("/api/v1/import/prometheus", b'other{idx="9"} 1\n')
        code, body = app.get("/api/v1/status/tsdb",
                             **{"match[]": "rw_metric",
                                "focusLabel": "idx"})
        data = json.loads(body)["data"]
        assert data["totalSeries"] == 4  # `other` filtered out
        focus = {e["name"]: e["count"]
                 for e in data["seriesCountByFocusLabelValue"]}
        assert focus == {"0": 1, "1": 1, "2": 1, "3": 1}

    def test_relabel_debug(self, app):
        cfg = ("- action: drop\n  source_labels: [idx]\n  regex: '1'\n"
               "- action: replace\n  target_label: dc\n"
               "  replacement: eu1\n")
        code, body = app.get("/metric-relabel-debug",
                             metric='m{idx="0"}', relabel_configs=cfg)
        assert code == 200
        d = json.loads(body)
        assert d["resultingLabels"]["dc"] == "eu1"
        assert len(d["steps"]) == 2 and not d["dropped"]
        code, body = app.get("/metric-relabel-debug",
                             metric='m{idx="1"}', relabel_configs=cfg)
        d = json.loads(body)
        assert d["dropped"] and d["steps"][0]["out"] is None

    def test_prettify_and_parse_query(self, app):
        code, body = app.get("/prettify-query",
                             query="sum(rate(m[5m]))by(job)")
        d = json.loads(body)
        assert d["status"] == "success" and "by (job)" in d["query"] \
            or "by(job)" in d["query"].replace(" ", "")
        code, body = app.get("/api/v1/parse-query",
                             query="sum(rate(m[5m]))")
        d = json.loads(body)
        assert d["status"] == "success"
        assert d["ast"]["kind"] == "AggrFuncExpr"
        kinds = []

        def walk(n):
            kinds.append(n["kind"])
            for c in n.get("children", []):
                walk(c)
        walk(d["ast"])
        assert "RollupExpr" in kinds or "FuncExpr" in kinds
        code, body = app.get("/prettify-query", query="sum((")
        assert json.loads(body)["status"] == "error"

    def test_delete_series(self, app):
        ingest_remote_write(app)
        code, _ = app.post("/api/v1/admin/tsdb/delete_series", b"",
                           **{"match[]": 'rw_metric{idx="0"}'})
        assert code == 204
        res = app.query_range("rw_metric", T0 / 1e3, (T0 + 300_000) / 1e3, 15)
        assert len(res["data"]["result"]) == 3

    def test_federate(self, app):
        now = time.time()
        text = f'fm{{x="1"}} 3.5 {int(now * 1000)}\n'
        app.post("/api/v1/import/prometheus", text.encode())
        code, body = app.get("/federate", **{"match[]": "fm"})
        assert code == 200
        assert b'fm{x="1"} 3.5' in body

    def test_top_and_active_queries(self, app):
        ingest_remote_write(app)
        app.query("rw_metric", T0 / 1e3)
        code, body = app.get("/api/v1/status/top_queries")
        data = json.loads(body)
        assert any(e["query"] == "rw_metric" for e in data["topByCount"])
        code, body = app.get("/api/v1/status/active_queries")
        assert code == 200

    def test_metrics_page(self, app):
        ingest_remote_write(app)
        code, body = app.get("/metrics")
        assert code == 200
        assert b"vm_rows_inserted_total" in body

    def test_snapshots(self, app):
        ingest_remote_write(app)
        app.force_flush()
        code, body = app.get("/snapshot/create")
        name = json.loads(body)["snapshot"]
        code, body = app.get("/snapshot/list")
        assert name in json.loads(body)["snapshots"]
        code, _ = app.get("/snapshot/delete", snapshot=name)
        assert code == 200

    def test_errors(self, app):
        code, body = app.get("/api/v1/query")
        assert code == 422
        code, body = app.get("/api/v1/query_range", query="rate(",
                             start="0", end="1", step="15")
        assert code == 422
        assert json.loads(body)["status"] == "error"
        code, _ = app.get("/nope/nope")
        assert code == 404


class TestSubprocess:
    def test_real_process_lifecycle(self, tmp_path):
        """Spawn the actual vmsingle process, ingest, query, restart, verify
        persistence (the apptest way)."""
        app = VmSingleProc(str(tmp_path / "data"))
        c = Client(app.port)
        line = json.dumps({"metric": {"__name__": "persisted"},
                           "values": [7.0], "timestamps": [T0]})
        code, _ = c.post("/api/v1/import", line.encode())
        assert code == 204
        c.force_flush()
        res = c.query("persisted", T0 / 1e3 + 10)
        assert res["data"]["result"][0]["value"][1] == "7"
        app.stop()
        # restart on same data dir
        app2 = VmSingleProc(str(tmp_path / "data"))
        c2 = Client(app2.port)
        res = c2.query("persisted", T0 / 1e3 + 10)
        assert res["data"]["result"][0]["value"][1] == "7"
        app2.stop()


class TestTracingAndCache:
    def test_trace_embedded(self, app):
        ingest_remote_write(app)
        code, body = app.get("/api/v1/query_range", query="sum(rate(rw_metric[1m]))",
                             start=T0 / 1e3, end=(T0 + 300_000) / 1e3,
                             step=15, trace="1")
        d = json.loads(body)
        assert "trace" in d
        msgs = json.dumps(d["trace"])
        assert "fetch" in msgs and "rollup" in msgs
        assert d["trace"]["duration_msec"] >= 0

    def test_rollup_cache_hit_and_backfill_reset(self, app):
        from victoriametrics_tpu.query.rollup_result_cache import GLOBAL
        GLOBAL.reset()
        ingest_remote_write(app)
        q = dict(query="rw_metric", start=T0 / 1e3,
                 end=(T0 + 300_000) / 1e3, step=15)
        r1 = app.get("/api/v1/query_range", **q)[1]
        h0 = GLOBAL.hits
        r2 = app.get("/api/v1/query_range", **q)[1]
        assert GLOBAL.hits > h0          # second run hits the cache
        assert json.loads(r1)["data"] == json.loads(r2)["data"]
        # backfill (old timestamps) resets the cache
        line = json.dumps({"metric": {"__name__": "rw_metric", "idx": "0"},
                           "values": [1.0], "timestamps": [T0 - 86_400_000]})
        app.post("/api/v1/import", line.encode())
        assert GLOBAL.stats()["entries"] == 0


class TestIngestServersAndGate:
    def test_tcp_udp_line_protocols(self, tmp_path):
        import socket

        from victoriametrics_tpu.apps.vmsingle import build, parse_flags
        args = parse_flags([f"-storageDataPath={tmp_path}/d",
                            "-httpListenAddr=127.0.0.1:0",
                            "-graphiteListenAddr=127.0.0.1:0",
                            "-opentsdbListenAddr=127.0.0.1:0"])
        storage, srv, api = build(args)
        srv.start()
        try:
            c = Client(srv.port)
            gport = api.ingest_servers[0].port
            oport = api.ingest_servers[1].port
            # graphite over TCP
            s = socket.create_connection(("127.0.0.1", gport), timeout=5)
            s.sendall(f"tcp.metric;src=tcp 5.5 {T0 // 1000}\n".encode())
            s.close()
            # graphite over UDP
            u = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            u.sendto(f"udp.metric 6.5 {T0 // 1000}\n".encode(),
                     ("127.0.0.1", gport))
            u.close()
            # opentsdb telnet over TCP
            s = socket.create_connection(("127.0.0.1", oport), timeout=5)
            s.sendall(f"put ot.tcp {T0 // 1000} 7.5 k=v\n".encode())
            s.close()
            deadline = time.time() + 10
            got = {}
            while time.time() < deadline and len(got) < 3:
                for name in ("tcp.metric", "udp.metric", "ot.tcp"):
                    res = c.query(f'{{__name__="{name}"}}', T0 / 1e3 + 10)
                    if res["data"]["result"]:
                        got[name] = res["data"]["result"][0]["value"][1]
                time.sleep(0.2)
            assert got == {"tcp.metric": "5.5", "udp.metric": "6.5",
                           "ot.tcp": "7.5"}
        finally:
            srv.stop()
            for isrv in api.ingest_servers:
                isrv.stop()
            storage.close()

    def test_concurrency_gate_rejects_with_429(self, tmp_path):
        """A saturated 1-slot gate must reject HTTP queries with 429 +
        Retry-After through the real endpoint."""
        from victoriametrics_tpu.apps.vmsingle import build, parse_flags
        from victoriametrics_tpu.httpapi.prometheus_api import ConcurrencyGate
        args = parse_flags([f"-storageDataPath={tmp_path}/d",
                            "-httpListenAddr=127.0.0.1:0"])
        storage, srv, api = build(args)
        api.gate = ConcurrencyGate(max_concurrent=1, max_queue_duration_s=0.2)
        srv.start()
        try:
            c = Client(srv.port)
            with api.gate:  # hold the only slot
                code, body = c.get("/api/v1/query", query="up")
                assert code == 429, body
                assert json.loads(body)["errorType"] == "too_many_requests"
            code, _ = c.get("/api/v1/query", query="up")
            assert code == 200  # slot released
            assert api.gate.rejected == 1
        finally:
            srv.stop()
            storage.close()

    def test_relative_time_param(self, app):
        import time as _t
        now = _t.time()
        line = f"rel_metric 9.5 {int((now - 60) * 1000)}\n"
        app.post("/api/v1/import/prometheus", line.encode())
        code, body = app.get("/api/v1/query", query="rel_metric")
        assert code == 200
        code, body = app.get("/api/v1/query_range", query="rel_metric",
                             start="-5m", end=str(now), step="15")
        assert code == 200
        assert json.loads(body)["data"]["result"]


class TestOTLP:
    def _build_payload(self):
        """Hand-build an ExportMetricsServiceRequest with a gauge, a
        cumulative sum and a histogram using the protowire writer."""
        import struct

        from victoriametrics_tpu.ingest.protowire import (w_bytes, w_tag,
                                                          w_varint)

        def kv(key, val):
            b = bytearray()
            w_bytes(b, 1, key.encode())
            av = bytearray()
            w_bytes(av, 1, val.encode())
            w_bytes(b, 2, bytes(av))
            return bytes(b)

        def fixed64(buf, fnum, u):
            w_tag(buf, fnum, 1)
            buf += struct.pack("<Q", u)

        def num_dp(ts_ns, val, attrs=()):
            dp = bytearray()
            fixed64(dp, 3, ts_ns)
            w_tag(dp, 4, 1)
            dp += struct.pack("<d", val)
            for k, v in attrs:
                w_bytes(dp, 7, kv(k, v))
            return bytes(dp)

        def metric_gauge(name, dp):
            m = bytearray()
            w_bytes(m, 1, name.encode())
            g = bytearray()
            w_bytes(g, 1, dp)
            w_bytes(m, 5, bytes(g))
            return bytes(m)

        def metric_hist(name, ts_ns):
            dp = bytearray()
            fixed64(dp, 3, ts_ns)
            fixed64(dp, 4, 10)               # count
            w_tag(dp, 5, 1)
            dp += struct.pack("<d", 55.5)    # sum
            w_bytes(dp, 6, struct.pack("<QQQ", 6, 3, 1))   # bucket counts
            w_bytes(dp, 7, struct.pack("<dd", 0.1, 1.0))   # bounds
            m = bytearray()
            w_bytes(m, 1, name.encode())
            h = bytearray()
            w_bytes(h, 1, bytes(dp))
            w_bytes(m, 9, bytes(h))
            return bytes(m)

        ts_ns = T0 * 1_000_000
        sm = bytearray()
        w_bytes(sm, 2, metric_gauge("otlp.gauge",
                                    num_dp(ts_ns, 3.5, [("env", "dev")])))
        w_bytes(sm, 2, metric_hist("otlp.latency", ts_ns))
        resource = bytearray()
        w_bytes(resource, 1, kv("service.name", "svc1"))
        rm = bytearray()
        w_bytes(rm, 1, bytes(resource))
        w_bytes(rm, 2, bytes(sm))
        req = bytearray()
        w_bytes(req, 1, bytes(rm))
        return bytes(req)

    def test_otlp_ingest(self, app):
        code, body = app.post("/opentelemetry/v1/metrics",
                              self._build_payload())
        assert code == 200, body
        res = app.query('{__name__="otlp.gauge"}', T0 / 1e3 + 10)
        r = res["data"]["result"][0]
        assert r["value"][1] == "3.5"
        assert r["metric"]["env"] == "dev"
        assert r["metric"]["service.name"] == "svc1"
        # histogram expansion works with histogram_quantile
        res = app.query('{__name__="otlp.latency_bucket", le="0.1"}', T0 / 1e3 + 10)
        assert res["data"]["result"][0]["value"][1] == "6"
        res = app.query('{__name__="otlp.latency_count"}', T0 / 1e3 + 10)
        assert res["data"]["result"][0]["value"][1] == "10"
        res = app.query(
            'histogram_quantile(0.5, {__name__="otlp.latency_bucket"})', T0 / 1e3 + 10)
        v = float(res["data"]["result"][0]["value"][1])
        assert 0 < v <= 0.1

    def test_otlp_garbage(self, app):
        code, _ = app.post("/v1/metrics", b"\x01\x02 not a protobuf")
        assert code == 400


class TestSeriesLimitsAndPush:
    def test_series_limits_drop(self, tmp_path):
        from victoriametrics_tpu.apps.vmsingle import build, parse_flags
        args = parse_flags([f"-storageDataPath={tmp_path}/d",
                            "-httpListenAddr=127.0.0.1:0",
                            "-maxLabelsPerTimeseries=3"])
        storage, srv, api = build(args)
        srv.start()
        try:
            c = Client(srv.port)
            ok = f'fits{{a="1"}} 1 {T0}\n'
            bad = f'toomany{{a="1",b="2",c="3",d="4"}} 1 {T0}\n'
            code, _ = c.post("/api/v1/import/prometheus", (ok + bad).encode())
            assert code == 204
            assert c.query("fits", T0 / 1e3 + 5)["data"]["result"]
            assert not c.query("toomany", T0 / 1e3 + 5)["data"]["result"]
            code, body = c.get("/metrics")
            assert b'vm_rows_ignored_total{reason="too_many_labels"} 1' in body
        finally:
            srv.stop()
            storage.close()

    def test_pushmetrics(self, tmp_path):
        from victoriametrics_tpu.httpapi.server import HTTPServer, Response
        from victoriametrics_tpu.utils.pushmetrics import MetricsPusher
        got = []
        sink = HTTPServer("127.0.0.1", 0)
        sink.route("/push", lambda req: (got.append(req.body),
                                         Response.text("OK"))[1])
        sink.start()
        p = MetricsPusher([f"http://127.0.0.1:{sink.port}/push"],
                          lambda: "m1 42\nm2{x=\"y\"} 7\n",
                          interval_s=0.2, extra_labels='job="t"')
        p.start()
        deadline = time.time() + 10
        while not got and time.time() < deadline:
            time.sleep(0.1)
        p.stop()
        sink.stop()
        assert got
        assert b'm1{job="t"} 42' in got[0]
        assert b'm2{job="t",x="y"} 7' in got[0]


class TestMultitenantHTTP:
    """Cluster-style /insert|/select/<accountID[:projectID]>/ routing."""

    def test_insert_select_tenant_paths(self, app):
        line = f"mt_metric{{t=\"a\"}} 41 {T0}\n"
        code, _ = app.post("/insert/7:3/prometheus/api/v1/import/prometheus",
                           line.encode())
        assert code == 204
        code, _ = app.post("/insert/8/prometheus/api/v1/import/prometheus",
                           f"mt_metric{{t=\"a\"}} 42 {T0}\n".encode())
        assert code == 204
        # tenant 7:3 sees only its own value
        code, body = app.get("/select/7:3/prometheus/api/v1/query",
                             query="mt_metric", time=str(T0 // 1000))
        assert code == 200, body
        res = json.loads(body)["data"]["result"]
        assert len(res) == 1 and res[0]["value"][1] == "41"
        # tenant 8 (project 0) sees its own
        code, body = app.get("/select/8/prometheus/api/v1/query",
                             query="mt_metric", time=str(T0 // 1000))
        assert json.loads(body)["data"]["result"][0]["value"][1] == "42"
        # default tenant sees nothing
        code, body = app.get("/api/v1/query",
                             query="mt_metric", time=str(T0 // 1000))
        assert json.loads(body)["data"]["result"] == []
        # tenants listing
        code, body = app.get("/admin/tenants")
        assert code == 200 and set(json.loads(body)["data"]) >= {"7:3", "8:0"}

    def test_bad_tenant_rejected(self, app):
        code, _ = app.post("/insert/xx/prometheus/api/v1/import/prometheus",
                           b"m 1\n")
        assert code == 400
        code, _ = app.get("/select/1:2")
        assert code == 400

    def test_rollup_cache_is_tenant_scoped(self, app):
        # regression: query_range results must never be served across
        # tenants from the rollup result cache
        for tenant, v in (("7", "111"), ("8", "222")):
            code, _ = app.post(
                f"/insert/{tenant}/prometheus/api/v1/import/prometheus",
                f"leak{{x=\"y\"}} {v} {T0}\n".encode())
            assert code == 204
        out = {}
        for tenant in ("7", "8"):
            code, body = app.get(
                f"/select/{tenant}/prometheus/api/v1/query_range",
                query="leak", start=str(T0 // 1000),
                end=str(T0 // 1000 + 60), step="30")
            res = json.loads(body)["data"]["result"]
            out[tenant] = res[0]["values"][0][1]
        assert out == {"7": "111", "8": "222"}
        # default tenant: nothing, even after both cached
        code, body = app.get("/api/v1/query_range", query="leak",
                             start=str(T0 // 1000),
                             end=str(T0 // 1000 + 60), step="30")
        assert json.loads(body)["data"]["result"] == []


class TestVMUI:
    def test_vmui_served(self, app):
        code, body = app.get("/vmui")
        assert code == 200
        text = body.decode()
        assert "<title>vmui" in text
        # the explorer drives these APIs; they must exist
        for ep in ("/api/v1/status/tsdb", "/api/v1/status/top_queries"):
            code, body = app.get(ep)
            assert code == 200, ep


class TestNativeExport:
    def test_roundtrip(self, app, tmp_path):
        ingest_remote_write(app, n_series=3, n_samples=10)
        code, body = app.get("/api/v1/export/native",
                             **{"match[]": "rw_metric"})
        assert code == 200 and body.startswith(b"vmtpu-native-v1\n")
        # import into a second instance
        from victoriametrics_tpu.apps.vmsingle import build, parse_flags
        args = parse_flags([f"-storageDataPath={tmp_path}/native2",
                            "-httpListenAddr=127.0.0.1:0"])
        storage2, srv2, _ = build(args)
        srv2.start()
        try:
            c2 = Client(srv2.port)
            code, _ = c2.post("/api/v1/import/native", body)
            assert code == 204
            r = c2.query_range("rw_metric", T0 / 1e3,
                               (T0 + 300_000) / 1e3, 15)
            assert len(r["data"]["result"]) == 3
            vals = r["data"]["result"][0]["values"]
            # 10 raw samples land on the grid with lookback fill
            assert {v for _, v in vals} == {str(i) for i in range(10)}
        finally:
            srv2.stop()
            storage2.close()

    def test_bad_header(self, app):
        code, _ = app.post("/api/v1/import/native", b"garbage")
        assert code == 400


class TestMetadataAndZabbix:
    def test_zabbix_connector_history(self, app):
        line = json.dumps({
            "host": {"host": "zhost", "name": "Zabbix Host"},
            "name": "system.cpu.load", "value": 1.25,
            "clock": T0 // 1000, "ns": 500000,
            "item_tags": [{"tag": "component", "value": "cpu"}]})
        code, _ = app.post("/zabbixconnector/api/v1/history", line.encode())
        assert code == 204
        r = app.query('{host="zhost"}', T0 / 1e3)
        res = r["data"]["result"][0]
        assert res["metric"]["__name__"] == "system.cpu.load"
        assert res["metric"]["tag_component"] == "cpu"
        assert res["value"][1] == "1.25"

    def test_type_help_metadata(self, app):
        body = (b"# HELP my_counter Counts the things.\n"
                b"# TYPE my_counter counter\n"
                b"my_counter 5\n")
        code, _ = app.post("/api/v1/import/prometheus", body)
        assert code == 204
        code, body = app.get("/api/v1/metadata")
        d = json.loads(body)["data"]
        assert d["my_counter"] == [{"type": "counter",
                                    "help": "Counts the things.",
                                    "unit": ""}]
        code, body = app.get("/api/v1/metadata", metric="my_counter")
        assert list(json.loads(body)["data"]) == ["my_counter"]

    def test_metric_names_stats(self, app):
        ingest_remote_write(app, n_series=2, n_samples=3)
        app.query("rw_metric", T0 / 1e3)
        code, body = app.get("/api/v1/status/metric_names_stats")
        recs = json.loads(body)["records"]
        # storage-authoritative stats count one hit per distinct name per
        # query (reference lib/storage/metricnamestats semantics)
        assert any(r["metricName"] == "rw_metric" and r["requestsCount"] >= 1
                   for r in recs)


class TestOpsEndpoints:
    def test_flags_page(self, app):
        code, body = app.get("/flags")
        assert code == 200 and b"storageDataPath=" in body

    def test_pprof_threads(self, app):
        code, body = app.get("/debug/pprof/goroutine")
        assert code == 200 and b"Thread" in body

    def test_tenant_metrics(self, app):
        app.post("/insert/3:4/prometheus/api/v1/import/prometheus",
                 f"tm_m 1 {T0}\n".encode())
        code, body = app.get("/metrics")
        assert b'vm_tenant_inserted_rows_total{accountID="3",projectID="4"} 1' \
            in body

    def test_tls_server(self, tmp_path):
        import ssl, subprocess, urllib.request
        cert = tmp_path / "cert.pem"
        key = tmp_path / "key.pem"
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-keyout",
             str(key), "-out", str(cert), "-days", "1", "-nodes", "-subj",
             "/CN=localhost"], check=True, capture_output=True)
        from victoriametrics_tpu.apps.vmsingle import build, parse_flags
        args = parse_flags([f"-storageDataPath={tmp_path}/tls", "-tls",
                            f"-tlsCertFile={cert}", f"-tlsKeyFile={key}",
                            "-httpListenAddr=127.0.0.1:0"])
        storage, srv, api = build(args)
        srv.start()
        try:
            ctx = ssl.create_default_context()
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
            with urllib.request.urlopen(
                    f"https://127.0.0.1:{srv.port}/health",
                    context=ctx, timeout=10) as r:
                assert r.read() == b"OK"
        finally:
            srv.stop()
            storage.close()


class TestMatrixWriter:
    """``query_range``'s body, written by the native matrix writer or by
    its Python fallback, is byte for byte one ``json.dumps`` over the
    tree of rows the evaluator answers."""

    QUERY = 'mw_metric'
    START, END, STEP = T0, T0 + 600_000, 20_000  # on the step's grid

    @pytest.fixture()
    def served(self, app):
        lines = []
        for i, esc in enumerate(['plain', 'q\\"uote\\\\back', 'é\\nline']):
            # row 1 starts late, row 2 ends early: absent points at both ends
            for j in [range(41), range(20, 41), range(10)][i]:
                v = [j / 7.0, float(j), float(np.float32(j * 0.3))][i]
                lines.append(f'mw_metric{{idx="{i}",esc="{esc}"}} {v!r} '
                             f'{T0 + j * 15_000}')
        code, resp = app.post("/api/v1/import/prometheus",
                              "\n".join(lines).encode())
        assert code == 204, resp
        return app, app.api

    def _counted(self, client):
        code, text = client.get("/metrics")
        assert code == 200
        return {w: int(float(line.rsplit(" ", 1)[1]))
                for line in text.decode().splitlines()
                for w in ("native", "python")
                if line.startswith(
                    f'vm_http_matrix_points_total{{writer="{w}"}}')}

    @pytest.mark.requires_native
    @pytest.mark.parametrize("masked", [False, True],
                             ids=["native", "native_masked"])
    @pytest.mark.parametrize("trace", ["", "1"], ids=["plain", "trace"])
    def test_body_is_the_tree_dump(self, served, monkeypatch, trace, masked):
        from tests.apptest_helpers import tree_matrix_body
        from victoriametrics_tpu import native
        from victoriametrics_tpu.query.exec import exec_query
        client, api = served
        if masked:
            monkeypatch.setattr(native, "available", lambda: False)
        before = self._counted(client)
        code, body = client.get(
            "/api/v1/query_range", query=self.QUERY, start=self.START / 1e3,
            end=self.END / 1e3, step=self.STEP // 1000, nocache="1",
            **({"trace": "1"} if trace else {}))
        assert code == 200, body
        after = self._counted(client)

        ec = api._ec(self.START, self.END, self.STEP)
        ec.disable_cache = True
        rows = exec_query(ec, self.QUERY)
        answer = json.loads(body)
        assert ("trace" in answer) == bool(trace)
        want = tree_matrix_body(
            ec.timestamps() / 1e3, rows,
            {"status": "success", "isPartial": False,
             "partialResolution": False}, answer.get("trace"))
        assert body == want
        assert b'q\\"uote\\\\back' in body and b"\\u00e9\\nline" in body
        points = sum(len(r["values"]) for r in answer["data"]["result"])
        assert 0 < points < len(rows) * ec.n_points  # absent points skipped
        wrote, idle = ("python", "native") if masked else ("native", "python")
        assert after[wrote] - before[wrote] == points
        assert after[idle] == before[idle]
