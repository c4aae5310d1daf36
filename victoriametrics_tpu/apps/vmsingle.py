"""vmsingle: the single-binary server (reference app/victoria-metrics/
main.go:53-125) — storage + query engine + HTTP API in one process.

Flags follow the reference's conventions (-storageDataPath,
-httpListenAddr, -retentionPeriod, -dedup.minScrapeInterval); every flag is
also settable via env var VM_<FLAGNAME> (lib/envflag analog).

Run: python -m victoriametrics_tpu.apps.vmsingle -storageDataPath=/tmp/vm
"""

from __future__ import annotations

import argparse
import os
import signal
import sys

from ..utils import logger


def parse_flags(argv=None):
    p = argparse.ArgumentParser(prog="vmsingle", prefix_chars="-")
    p.add_argument("-storageDataPath", default="victoria-metrics-data")
    p.add_argument("-httpListenAddr", default=":8428")
    p.add_argument("-retentionPeriod", default="13m",
                   help="duration: 30d, 13m(onths) etc")
    p.add_argument("-dedup.minScrapeInterval", dest="dedup_interval",
                   default="0s")
    p.add_argument("-storage.maxHourlySeries", dest="max_hourly_series",
                   type=int, default=0)
    p.add_argument("-storage.maxDailySeries", dest="max_daily_series",
                   type=int, default=0)
    p.add_argument("-search.maxUniqueTimeseries", dest="max_series",
                   type=int, default=300_000)
    p.add_argument("-search.maxSamplesPerQuery", dest="max_samples_per_query",
                   type=int, default=1_000_000_000)
    p.add_argument("-search.maxMemoryPerQuery", dest="max_memory_per_query",
                   type=int, default=0)
    p.add_argument("-search.maxQueryDuration", dest="max_query_duration",
                   default="30s")
    p.add_argument("-search.maxStalenessInterval", dest="lookback",
                   default="5m")
    p.add_argument("-search.tpuBackend", dest="tpu", action="store_true",
                   help="route supported rollups to the TPU")
    p.add_argument("-graphiteListenAddr", dest="graphite_addr", default="")
    p.add_argument("-influxListenAddr", dest="influx_addr", default="")
    p.add_argument("-opentsdbListenAddr", dest="opentsdb_addr", default="")
    p.add_argument("-relabelConfig", dest="relabel_config", default="",
                   help="path to global relabeling rules YAML")
    p.add_argument("-streamAggr.config", dest="streamaggr_config", default="",
                   help="path to stream aggregation config YAML")
    p.add_argument("-streamAggr.keepInput", dest="streamaggr_keep_input",
                   action="store_true")
    p.add_argument("-maxLabelsPerTimeseries", type=int, default=40)
    p.add_argument("-maxLabelValueLen", type=int, default=4096)
    p.add_argument("-maxIngestionRate", dest="max_ingestion_rate",
                   type=int, default=0,
                   help="rows/s ingest ceiling, 0 = unlimited "
                        "(lib/ratelimiter analog: bursts within ~1s are "
                        "smoothed by blocking; sustained overload gets "
                        "429 + Retry-After)")
    p.add_argument("-maxTenantIngestionRate",
                   dest="max_tenant_ingestion_rate", type=int, default=0,
                   help="per-tenant rows/s ingest ceiling, 0 = unlimited")
    p.add_argument("-selfScrapeInterval", dest="self_scrape_interval",
                   default="",
                   help="scrape own /metrics into storage every "
                        "interval (15s when set to 1); empty/0 = off")
    p.add_argument("-pushmetrics.url", dest="pushmetrics_urls",
                   action="append", default=[])
    p.add_argument("-pushmetrics.interval", dest="pushmetrics_interval",
                   default="10s")
    p.add_argument("-pushmetrics.extraLabel", dest="pushmetrics_extra",
                   default="")
    p.add_argument("-rule", action="append", default=[],
                   help="vmalert-format rule file evaluated SERVER-SIDE "
                        "through the materialized-stream engine (rules "
                        "sharing an expression share one fetch+rollup "
                        "per interval); repeatable")
    p.add_argument("-evaluationInterval", dest="eval_interval",
                   default="1m")
    p.add_argument("-loggerLevel", default="INFO")
    p.add_argument("-tls", action="store_true")
    p.add_argument("-tlsCertFile", default="")
    p.add_argument("-tlsKeyFile", default="")
    args, _ = p.parse_known_args(argv)
    # env overrides: VM_STORAGEDATAPATH etc (envflag analog)
    for name in vars(args):
        env = os.environ.get("VM_" + name.upper().replace(".", "_"))
        if env is not None:
            cur = getattr(args, name)
            if isinstance(cur, bool):
                setattr(args, name, env not in ("0", "false", ""))
            elif isinstance(cur, list):
                setattr(args, name, [x for x in env.split(",") if x])
            else:
                setattr(args, name, type(cur)(env))
    return args


def _dur_ms(s: str, months_ok=False) -> int:
    from ..query.metricsql.parser import parse_duration_ms
    s = s.strip()
    if months_ok and s.endswith("m") and s[:-1].isdigit():
        # retentionPeriod bare "13m" means months per reference semantics
        return int(float(s[:-1]) * 31 * 86_400_000)
    ms, step_based = parse_duration_ms(s)
    return int(ms)


def _attach_tpu_engine(api, enabled: bool):
    """-search.tpuBackend startup, BEFORE the listener comes up: initialise
    JAX in this process (a chip belongs to one process at a time — a child
    that touched it first would take it from the server), build the engine
    on whatever backend JAX gives under the environment the server was
    started with (JAX_PLATFORMS=cpu serves the same path from XLA-CPU, for
    tests), pre-compile the hot kernels, attach.  When `build` returns the
    engine is attached.  Any failure is logged and raised: a server asked
    for the device path exits non-zero, it never serves from the host
    instead."""
    if not enabled:
        return
    try:
        from ..query.tpu_engine import (TPUEngine, auto_mesh, init_backend,
                                        warmup)
        devs = init_backend()
        logger.infof("device backend: %d %s device(s) (%s)", len(devs),
                     devs[0].platform, devs[0].device_kind)
        engine = TPUEngine(mesh=auto_mesh())
        # also seeds the persistent compilation cache: restarts stay warm
        n = warmup(engine)
    except Exception as e:
        logger.errorf("-search.tpuBackend: the device engine could not "
                      "start (%r); exiting", e)
        raise
    api.tpu = engine
    logger.infof("tpu engine attached (%s tiles, %d warmup kernels)",
                 "f32" if engine.is_f32() else "f64", n)


def build(args):
    from ..httpapi.prometheus_api import PrometheusAPI
    from ..httpapi.server import HTTPServer
    from ..storage.storage import Storage

    retention = _dur_ms(args.retentionPeriod, months_ok=True)
    dedup = _dur_ms(args.dedup_interval) if args.dedup_interval != "0s" else 0
    storage = Storage(args.storageDataPath, retention_ms=retention,
                      dedup_interval_ms=dedup,
                      max_hourly_series=args.max_hourly_series,
                      max_daily_series=args.max_daily_series)
    relabel = None
    if args.relabel_config:
        from ..ingest.relabel import parse_relabel_configs
        relabel = parse_relabel_configs(open(args.relabel_config).read())
    stream_aggr = None
    if args.streamaggr_config:
        from ..ingest.streamaggr import load_from_text
        stream_aggr = load_from_text(open(args.streamaggr_config).read(),
                                     lambda rows: storage.add_rows(rows))
        stream_aggr.start()
    host, _, port = args.httpListenAddr.rpartition(":")
    srv = HTTPServer(host or "0.0.0.0", int(port),
                     tls_cert_file=args.tlsCertFile if args.tls else "",
                     tls_key_file=args.tlsKeyFile if args.tls else "")
    from ..ingest.serieslimits import SeriesLimits
    limits = SeriesLimits(max_labels_per_series=args.maxLabelsPerTimeseries,
                          max_label_value_len=args.maxLabelValueLen)
    rate_limiter = None
    if args.max_ingestion_rate > 0 or args.max_tenant_ingestion_rate > 0:
        from ..ingest.ratelimiter import TenantRateLimiters
        rate_limiter = TenantRateLimiters(
            global_limit=args.max_ingestion_rate,
            per_tenant_limit=args.max_tenant_ingestion_rate)
    api = PrometheusAPI(storage, None,
                        lookback_delta=_dur_ms(args.lookback),
                        max_series=args.max_series,
                        relabel_configs=relabel, stream_aggr=stream_aggr,
                        stream_aggr_keep_input=args.streamaggr_keep_input,
                        series_limits=limits,
                        max_samples_per_query=args.max_samples_per_query,
                        max_memory_per_query=args.max_memory_per_query,
                        max_query_duration_ms=_dur_ms(
                            args.max_query_duration),
                        rate_limiter=rate_limiter)
    _attach_tpu_engine(api, args.tpu)
    api.flags_map = {k: v for k, v in vars(args).items()}
    api.register(srv)
    from ..utils import profiler
    profiler.ensure_started()
    # self-monitoring plane: own registry -> own storage as real series;
    # the SLO engine's burn-rate evals ride each scrape tick
    from ..utils import selfscrape
    api.selfscraper = selfscrape.maybe_start(
        storage.add_rows, "vmsingle", int(port),
        flag_value=args.self_scrape_interval, extra=api.app_metrics,
        on_tick=lambda now_ms: api.init_sloplane().maybe_eval(now_ms))
    from ..httpapi.graphite_api import GraphiteAPI
    GraphiteAPI(storage).register(srv)
    if args.pushmetrics_urls:
        from ..utils.pushmetrics import MetricsPusher
        api.pusher = MetricsPusher(
            args.pushmetrics_urls,
            lambda: api.h_metrics(None).body.decode(),
            interval_s=_dur_ms(args.pushmetrics_interval) / 1e3,
            extra_labels=args.pushmetrics_extra)
        api.pusher.start()
    api.rule_groups = []
    if getattr(args, "rule", None):
        # server-side recording/alerting rules (the reference evaluates
        # recording rules in vmalert against vmselect; here they run
        # in-process through the shared materialized-stream engine, so
        # rules and watch subscribers amortize one evaluation per
        # distinct expression)
        import yaml

        from ..httpapi.server import Response as _Resp
        from . import vmalert as vmalert_mod
        ds = vmalert_mod.EngineDatasource(api)
        rw = vmalert_mod.LocalWriter(api)
        for path in args.rule:
            cfg = yaml.safe_load(open(path).read()) or {}
            for g in cfg.get("groups", []):
                api.rule_groups.append(vmalert_mod.Group(
                    g, ds, [], rw,
                    vmalert_mod._dur_s(args.eval_interval, 60.0)))
        for g in api.rule_groups:
            g.start()
        srv.route("/api/v1/rules", lambda req: _Resp.json(
            {"status": "success",
             "data": {"groups": [g.api_dict()
                                 for g in api.rule_groups]}}))
        logger.infof("vmsingle: %d server-side rule group(s) armed",
                     len(api.rule_groups))
    api.ingest_servers = []
    for proto, addr in (("graphite", args.graphite_addr),
                        ("influx", args.influx_addr),
                        ("opentsdb", args.opentsdb_addr)):
        if addr:
            from ..ingest.ingestserver import IngestServer
            h, _, p_ = addr.rpartition(":")
            isrv = IngestServer(proto, h or "0.0.0.0", int(p_),
                                api._add_rows)
            isrv.start()
            api.ingest_servers.append(isrv)
    return storage, srv, api


def main(argv=None):
    import threading
    import faulthandler
    faulthandler.register(signal.SIGUSR1)

    args = parse_flags(argv)
    logger.set_level(args.loggerLevel)
    storage, srv, _api = build(args)
    logger.infof("vmsingle started: data=%s listen=%s",
                 args.storageDataPath, args.httpListenAddr)

    # serve from a daemon thread; the main thread blocks on the stop event.
    # Calling HTTPServer.shutdown() from inside a signal handler interrupting
    # serve_forever deadlocks (shutdown() joins the loop it interrupted).
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())

    def _reload(*_):
        # SIGHUP hot-reload of -relabelConfig and -streamAggr.config
        try:
            if args.relabel_config:
                from ..ingest.relabel import parse_relabel_configs
                _api.relabel = parse_relabel_configs(
                    open(args.relabel_config).read())
            if args.streamaggr_config:
                from ..ingest.streamaggr import load_from_text
                new = load_from_text(
                    open(args.streamaggr_config).read(),
                    lambda rows: storage.add_rows(rows))
                old = _api.stream_aggr
                new.start()
                _api.stream_aggr = new
                if old is not None:
                    old.stop()
            logger.infof("vmsingle: config reloaded")
        except Exception as e:
            logger.errorf("vmsingle: reload failed, keeping old config: %s",
                          e)
    signal.signal(signal.SIGHUP, _reload)
    srv.start()
    try:
        while not stop.wait(1.0):
            pass
    finally:
        logger.infof("vmsingle: shutting down")
        for g in getattr(_api, "rule_groups", []):
            g.stop()
        srv.stop()
        for isrv in getattr(_api, "ingest_servers", []):
            isrv.stop()
        if getattr(_api, "pusher", None) is not None:
            _api.pusher.stop()
        if getattr(_api, "selfscraper", None) is not None:
            # before storage.close(): a late scrape must not write into
            # a closed storage
            _api.selfscraper.stop()
        if _api.stream_aggr is not None:
            # final window flush BEFORE storage closes (streamaggr MustStop
            # ordering): dropping the open window on every restart would
            # lose data, and a late flusher tick must not write into a
            # closed storage
            _api.stream_aggr.stop(final_flush=True)
        storage.close()
        logger.infof("vmsingle: shutdown complete")


if __name__ == "__main__":
    main()
