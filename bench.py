"""Benchmark: END-TO-END samples/sec through the real served query path.

Workload modeled on BASELINE.md config 2 (`sum by(instance)(rate(m[5m]))`
range query over high-cardinality counters): ingest 8192 counter series x
1440 samples (6h @ 15s) into a real on-disk Storage (parts, index, codecs),
then serve the full evaluator — index search -> part block decode -> series
assembly -> device tiles -> fused rollup+aggregation.

Headline = STEADY-STATE serving rate for the realistic dashboard loop: the
window advances one step per refresh while live ingest appends new scrapes
between refreshes, and every refresh goes through the SAME cached range
executor the HTTP layer serves (result-cache tail merge over the full
eval stack). Each refresh therefore computes only the uncovered suffix —
fetch, rollup, aggregation — and merges it onto the cached prefix; a
built-in assert proves the served rows equal a cold nocache evaluation
(bit-for-bit on the f64 host path, within the f32 tile bound on device).
Neither backend can serve a pure cache hit: every refresh sees new bounds
AND new data. Cold (nocache first query, incl. jit compile) and ingest
rates are reported inside the metric label.

Backend policy — LOUD, never silent: JAX is initialised in this process
and the device engine is built on whatever backend it gives; the JSON
records it as "backend" ("tpu-float32" / "cpu-device-float64") with the
platform, device kind and count under "device". A device engine that
cannot start fails the bench. Tile dtype follows the engine's auto rule:
f32 rebased tiles on real TPU (f64 is emulated there; error bounds in
tests/test_f32_tiles.py), f64 on CPU-XLA.

Throughput accounting: each refresh logically serves the samples a cold
evaluation of that window would scan (series x fetch-range samples); the
rate divides that by the measured p50 refresh latency.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "samples/sec", "vs_baseline": N,
   "backend": ..., "refresh_p50_ms": N, "refresh_p99_ms": N,
   "refresh_ms": [per-refresh latencies], "cache": {inplace/rebuild/
   merge_seconds/merge_gate_yields}, "flight": {per-leg flight-recorder
   attribution: slow-refresh captures + the slowest one's overlap
   summary}, "cost": {per-refresh CostTracker split: samples/bytes/
   cpu-ms + wall/cpu by phase + wall_accounted_pct >= 90}, "profiler":
   {sample count at VM_PROFILE_HZ — the run is measured with the
   continuous profiler AND cost accounting ON}}
The refresh-latency DISTRIBUTION (p99 + the raw list) is part of the
artifact: the p50-vs-trace variance ROADMAP item 1 tracks is invisible
in a single median.

vs_baseline divides by 1e8 samples/sec — the order of the reference's
single-core block-unpack + rollup scan rate (its netstorage unpack workers
+ rollupConfig.Do; BASELINE.md notes the repo publishes capacity figures,
not absolute scan rates, so this is the documented working assumption).

A querytracer span tree for one steady-state refresh (and the cold query)
is written to bench_trace.json — the where-does-the-time-go artifact.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

N_SERIES = 8192
N_SAMPLES = 1440         # 6h @ 15s
N_INSTANCES = 256
STEP = 60_000
REFRESHES = 6
JITTER_MS = 2_000  # scrape-time jitter; the end0 ceil below depends on it

# per-phase attribution (vm_fetch_phase_seconds_total, storage + eval):
# deltas across a timed region divide the time between the fetch stages
# and the host rollup, so a bench round says WHERE a win/regression lives.
# "assemble_native" is the fused VM_NATIVE_ASSEMBLE kernel (one native
# fetch→decode→clip→float call per part); collect/decode only tick on the
# split fallback path.
PHASES = ("queue_wait", "index_search", "collect", "decode",
          "assemble_native", "assemble", "rollup")
# the write-path twin (vm_ingest_phase_seconds_total): where the live
# steady-state ingest spends its time, per refresh
ING_PHASES = ("resolve", "register", "append")


def _phase_totals() -> dict:
    from victoriametrics_tpu.utils import metrics as metricslib
    return {ph: metricslib.REGISTRY.float_counter(
        f'vm_fetch_phase_seconds_total{{phase="{ph}"}}').get()
        for ph in PHASES}


def _phase_label(d0: dict, d1: dict, n: int) -> str:
    """'qwait=0/idx=2/collect=0/decode=0/native=25/assemble=9/rollup=12ms'."""
    short = {"queue_wait": "qwait", "index_search": "idx",
             "collect": "collect", "decode": "decode",
             "assemble_native": "native", "assemble": "assemble",
             "rollup": "rollup"}
    parts = [f"{short[ph]}={(d1[ph] - d0[ph]) * 1e3 / max(n, 1):.0f}"
             for ph in PHASES]
    return "/".join(parts) + "ms"


def _device_plane_totals() -> dict:
    """Device link/residency counters (models.tile_cache): uploaded /
    downloaded bytes and resident-window hits — the residency win is
    upload_steady << upload_cold in the artifact."""
    from victoriametrics_tpu.models import tile_cache as tclib
    from victoriametrics_tpu.utils import metrics as metricslib
    return {
        "uploaded_bytes": tclib.bytes_uploaded(),
        "downloaded_bytes": tclib.bytes_downloaded(),
        "window_hits": metricslib.REGISTRY.counter(
            "vm_device_window_cache_hits_total").get(),
        "window_compactions": metricslib.REGISTRY.counter(
            "vm_device_window_compactions_total").get(),
    }


def _device_plane_delta(d0: dict) -> dict:
    return {k: v - d0[k] for k, v in _device_plane_totals().items()}


def _cache_merge_totals() -> dict:
    """Cumulative result-cache merge counters (see _cache_merge_delta)."""
    from victoriametrics_tpu.utils import metrics as metricslib
    return {
        "inplace": metricslib.REGISTRY.counter(
            "vm_rollup_cache_inplace_total").get(),
        "rebuild": metricslib.REGISTRY.counter(
            "vm_rollup_cache_rebuild_total").get(),
        "put_reuse": metricslib.REGISTRY.counter(
            "vm_rollup_cache_put_identity_reused_total").get(),
        "merge_seconds": metricslib.REGISTRY.float_counter(
            "vm_rollup_cache_merge_seconds_total").get(),
        "merge_gate_yields": metricslib.REGISTRY.counter(
            "vm_merge_gate_yields_total").get(),
    }


def _cache_merge_delta(c0: dict) -> dict:
    """Result-cache merge handling DURING one backend's steady-state
    loop (acceptance: inplace > 0): deltas against the pre-loop
    snapshot, like the phase labels — absolute reads would fold the
    other backend leg's and warm-up activity into the winner's stats."""
    return {k: round(v - c0[k], 4) for k, v in
            _cache_merge_totals().items()}


def _ingest_phase_totals() -> dict:
    from victoriametrics_tpu.utils import metrics as metricslib
    return {ph: metricslib.ingest_phase(ph).get() for ph in ING_PHASES}


def _ingest_phase_label(d0: dict, d1: dict, n: int) -> str:
    """'resolve=3/register=0/append=1ms' of live ingest per refresh."""
    parts = [f"{ph}={(d1[ph] - d0[ph]) * 1e3 / max(n, 1):.0f}"
             for ph in ING_PHASES]
    return "/".join(parts) + "ms"


def _cost_leg_summary(costs, lat) -> dict:
    """Per-leg cost attribution from the refreshes' CostTrackers (the
    per-query accounting plane, utils/costacc): what one steady refresh
    scans/reads/burns, plus how much of the measured refresh wall time
    the named cost buckets account for (the honesty ratio — anything
    below ~90% means an unnamed phase is eating serving time)."""
    n = max(len(costs), 1)
    wall: dict = {}
    cpu: dict = {}
    samples = bytes_read = dev_up = dev_down = rpc = 0
    for c in costs:
        samples += c.samples
        bytes_read += c.part_bytes
        dev_up += c.device_up
        dev_down += c.device_down
        rpc += c.rpc_bytes
        for k, v in c.wall_ms.items():
            wall[k] = wall.get(k, 0.0) + v
        for k, v in c.cpu_ms.items():
            cpu[k] = cpu.get(k, 0.0) + v
    refresh_wall_ms = sum(lat) * 1e3
    return {
        "samples_scanned_per_refresh": samples // n,
        "bytes_read_per_refresh": bytes_read // n,
        "cpu_ms_per_refresh": round(sum(cpu.values()) / n, 2),
        "device_bytes_per_refresh": (dev_up + dev_down) // n,
        "rpc_bytes_per_refresh": rpc // n,
        "wall_ms_by_phase": {k: round(v / n, 2)
                             for k, v in sorted(wall.items())},
        "cpu_ms_by_phase": {k: round(v / n, 2)
                            for k, v in sorted(cpu.items())},
        "wall_accounted_pct": round(
            sum(wall.values()) / refresh_wall_ms * 100, 1)
        if refresh_wall_ms > 0 else 0.0,
    }


def _leg_flight_summary(id0: int, threshold_ms: float) -> dict:
    """Flight-recorder outcome of one backend leg: how many slow-refresh
    captures fired past `id0`, and the attribution summary of the
    slowest one.  When the whole loop stayed under the threshold, an
    on-demand capture of the still-live ring window stands in — the
    artifact always ships a timeline (ROADMAP item 1's open question is
    exactly "what overlapped the slow refresh", and the answer must not
    depend on the slow refresh happening to recur)."""
    from victoriametrics_tpu.utils import flightrec
    if not flightrec.enabled():
        return {"enabled": False}
    # fired counts every capture of the leg; the retention ring
    # (VM_FLIGHT_CAPTURES) bounds how many are still inspectable, so
    # the slowest RETAINED capture may not be the slowest fired —
    # "evicted" makes that truncation visible in the artifact
    fired = flightrec.RECORDER.total() - id0
    caps = [c for c in flightrec.RECORDER.list() if c["id"] > id0]
    source = "slow_refresh"
    if not caps:
        cap = flightrec.RECORDER.capture("bench_on_demand")
        caps = [c for c in flightrec.RECORDER.list()
                if c["id"] == cap["id"]]
        source = "on_demand"
    slowest = max(caps,
                  key=lambda c: (c.get("refresh_ms", 0.0), c["id"]))
    out = {"enabled": True, "threshold_ms": round(threshold_ms, 1),
           "captures": fired, "source": source,
           "capture_id": slowest["id"],
           "summary": slowest.get("summary", {})}
    if fired > len(caps):
        out["evicted"] = fired - len(caps)
    if "refresh_ms" in slowest:
        out["refresh_ms"] = slowest["refresh_ms"]
    return out


def _device_engine():
    """Initialise JAX in THIS process and build the device engine on
    whatever backend JAX gives. Returns (engine, backend_label,
    device_info); raises what JAX or the engine raises — a device leg
    that cannot start fails the bench, it is never relabelled and served
    from the host."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine, init_backend
    devs = init_backend()
    platform = devs[0].platform
    print(f"bench: {len(devs)} {platform} device(s) "
          f"({devs[0].device_kind})", file=sys.stderr)
    engine = TPUEngine()
    label = ("tpu" if platform == "tpu" else "cpu-device") \
        + f"-{np.dtype(engine.value_dtype).name}"
    return engine, label, {"platform": platform, "n_devices": len(devs),
                           "device_kind": devs[0].device_kind}


def _assert_rows_equal(a, b, rtol: float = 0.0) -> None:
    """Served (cached) rows must match a cold eval: bit-identical on the
    f64 host path (rtol=0, equal_nan covers NaN==NaN), within the f32
    tile error bound on the device path (see tests/test_f32_tiles.py —
    prefix and suffix tiles round independently). f64 DEVICE legs compare
    at rtol=1e-12: XLA compiles the suffix grid and the full-window grid
    separately and may order the group-sum reductions differently
    (measured ~2e-15 relative), so exact bit equality is only guaranteed
    on the host path; structural divergence still fails loudly."""
    da = {ts.metric_name.marshal(): ts.values for ts in a}
    db = {ts.metric_name.marshal(): ts.values for ts in b}
    assert set(da) == set(db), (len(da), len(db))
    for k, va in da.items():
        vb = db[k]
        if rtol == 0.0:
            ok = np.array_equal(va, vb, equal_nan=True)
        else:
            fa, fb = np.isnan(va), np.isnan(vb)
            m = ~fa
            ok = bool((fa == fb).all()) and bool(
                np.allclose(va[m], vb[m], rtol=rtol, equal_nan=True))
        assert ok, "served result diverged from cold evaluation"


_SELF_METRIC_FAMS = (
    "vm_selfscrape_scrapes_total", "vm_selfscrape_rows_total",
    "vm_selfscrape_errors_total", "vm_slo_evals_total",
    "vm_slo_eval_rounds_total", "vm_matstream_evals_total",
    "vm_gc_collections_total", "vm_log_messages_total",
)


def _self_metrics_totals() -> dict:
    """Key vm_* counters from the process registry, summed per family —
    the observability plane's own view of a bench leg."""
    from victoriametrics_tpu.utils import metrics as metricslib
    out: dict = {}
    for name, val in metricslib.REGISTRY.collect_values(
            include_process=False):
        fam = metricslib.split_name(name)[0]
        if fam in _SELF_METRIC_FAMS:
            out[fam] = out.get(fam, 0.0) + val
    return out


def _self_metrics_delta(t0: dict, t1: dict) -> dict:
    return {k: round(t1.get(k, 0.0) - t0.get(k, 0.0), 3)
            for k in sorted(set(t0) | set(t1))}


def main() -> None:
    from victoriametrics_tpu.query.exec import exec_query
    from victoriametrics_tpu.query.types import EvalConfig
    from victoriametrics_tpu.storage.storage import Storage
    from victoriametrics_tpu.utils.querytracer import Tracer

    # the continuous profiler runs for the WHOLE bench (acceptance: the
    # headline is measured with profiler + cost accounting ON)
    from victoriametrics_tpu.utils import profiler
    profiler.ensure_started()

    tmp = tempfile.mkdtemp(prefix="vmtpu-bench-")
    # anchor to wall clock so steady-state ingest is "live" data (the
    # result-cache backfill reset and retention behave as in production)
    now_ms = int(time.time() * 1000)
    t_start = (now_ms - (N_SAMPLES - 1) * 15_000) // STEP * STEP
    rng = np.random.default_rng(0)
    scraper = None
    try:
        s = Storage(tmp)

        # the self-monitoring plane runs for the WHOLE bench (acceptance:
        # the headline is measured with self-scrape + SLO engine ON): the
        # process's own registry lands in the bench storage as real
        # series, and burn-rate evals ride each scrape tick
        from victoriametrics_tpu.httpapi.prometheus_api import \
            PrometheusAPI as _PlaneAPI
        from victoriametrics_tpu.utils import selfscrape as _selfscrape
        from victoriametrics_tpu.utils.selfscrape import SelfScraper
        plane_api = _PlaneAPI(s)
        plane_engine = plane_api.init_sloplane()
        # VM_SELF_SCRAPE_INTERVAL=0 means OFF (the documented flag-table
        # semantics) — the plane-overhead A/B leg, NOT a 20Hz loop
        # (SelfScraper clamps interval_s to 0.05s, so passing 0 through
        # would measure the opposite of "plane disabled")
        scrape_interval = _selfscrape.configured_interval("5")
        if scrape_interval > 0:
            scraper = SelfScraper(
                s.add_rows, instance="bench", interval_s=scrape_interval,
                extra=plane_api.app_metrics,
                on_tick=lambda now_ms: plane_engine.maybe_eval(now_ms))
            scraper.start()
        else:
            print("bench: self-monitoring plane OFF "
                  "(VM_SELF_SCRAPE_INTERVAL=0) — plane-overhead A/B leg",
                  file=sys.stderr)

        # -- ingest: realistic jittered counters through the real write
        # path — the COLUMNAR pipeline HTTP ingest uses (raw text series
        # keys resolved by the native key map, no per-row Python)
        from victoriametrics_tpu import native
        base = np.arange(N_SAMPLES, dtype=np.int64) * 15_000 + t_start
        keys = [(f'http_requests_total{{idx="{i}",'
                 f'instance="host-{i % N_INSTANCES}",'
                 f'job="job-{i % 17}"}}').encode()
                for i in range(N_SERIES)]
        keybuf = b"".join(keys)
        klens = np.fromiter((len(k) for k in keys), np.int64, N_SERIES)
        koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
        last_val = np.zeros(N_SERIES)

        def columnar_rows(ts2, vals2):
            """(S, K) timestamp/value arrays -> one ColumnarRows batch."""
            k = ts2.shape[1]
            return native.ColumnarRows(
                keybuf, np.repeat(koffs, k), np.repeat(klens, k),
                ts2.reshape(-1).astype(np.int64), vals2.reshape(-1))

        t0 = time.perf_counter()
        chunk = 256  # series per batch: ~368k-row columnar batches
        for i0 in range(0, N_SERIES, chunk):
            i1 = min(i0 + chunk, N_SERIES)
            ts2 = np.sort(base[None, :] +
                          rng.integers(-JITTER_MS, JITTER_MS + 1, (i1 - i0, N_SAMPLES)),
                          axis=1)
            vals2 = np.cumsum(rng.integers(0, 50, (i1 - i0, N_SAMPLES)),
                              axis=1).astype(np.float64)
            last_val[i0:i1] = vals2[:, -1]
            cr = native.ColumnarRows(
                keybuf, np.repeat(koffs[i0:i1], N_SAMPLES),
                np.repeat(klens[i0:i1], N_SAMPLES),
                ts2.reshape(-1), vals2.reshape(-1))
            s.add_rows_columnar(cr)
        ingest_dt = time.perf_counter() - t0
        ingest_rate = N_SERIES * N_SAMPLES / ingest_dt
        s.force_flush()
        s.force_merge()

        tpu, backend_label, device_info = _device_engine()
        q = "sum by (instance)(rate(http_requests_total[5m]))"
        duration = (N_SAMPLES - 1) * 15_000 - 300_000
        # logical scan size of one window (series x fetch-range samples)
        samples = N_SERIES * ((duration + 600_000) // 15_000)

        def ingest_fresh(end_ms: int) -> None:
            """4 new scrapes per series in (end_ms - STEP, end_ms]."""
            incr = rng.integers(0, 50, (N_SERIES, 4))
            vals2 = last_val[:, None] + np.cumsum(incr, axis=1)
            last_val[:] = vals2[:, -1]
            ts2 = (end_ms - STEP +
                   (np.arange(4, dtype=np.int64) + 1)[None, :] * 15_000 +
                   rng.integers(-JITTER_MS, JITTER_MS + 1, (N_SERIES, 4)))
            ts2.sort(axis=1)
            s.add_rows_columnar(columnar_rows(ts2, vals2.astype(np.float64)))

        results = {}
        traces = {}
        flights = {}
        device_plane = None
        # an operator-set VM_SLOW_REFRESH_MS wins over the per-leg
        # calibration below (the env var is rewritten per leg otherwise)
        try:
            user_slow_refresh_ms = float(
                os.environ["VM_SLOW_REFRESH_MS"])
        except (KeyError, ValueError):
            user_slow_refresh_ms = None
        # first refresh window must start BEYOND every initial sample
        # (incl. jitter): rounding down would interleave the first fresh
        # scrapes with the initial batch's tail, fabricating counter
        # decreases that are resets to neither backend's credit
        end0 = t_start + -(-((N_SAMPLES - 1) * 15_000 + JITTER_MS)
                           // STEP) * STEP
        from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
        for backend, engine in (("device", tpu), ("host-batch", None)):
            # the result cache is process-global and NOT backend-keyed:
            # reset between legs so the host leg can't serve (or be
            # timed against) device-seeded entries
            from victoriametrics_tpu.query.rollup_result_cache import \
                GLOBAL as _rcache
            _rcache.reset()
            # steady-state refreshes go through the SAME cached executor
            # the HTTP layer serves (result-cache tail merge + full eval
            # stack) — this is the path a dashboard actually pays
            api = PrometheusAPI(s, engine)
            selfm0 = _self_metrics_totals()
            start = end0 - duration
            kw = dict(step=STEP, storage=s, tpu=engine)
            # cold: full fetch+decode+compute, result caches off, jit
            # compile included
            dev_cold0 = _device_plane_totals()
            tr = Tracer(True)
            t0 = time.perf_counter()
            rows = exec_query(EvalConfig(start=start, end=end0, **kw,
                                         disable_cache=True, tracer=tr),
                              q)
            cold_dt = time.perf_counter() - t0
            traces[backend + "-cold"] = tr.to_dict()
            # cold upload = the one full-window ship, measured BEFORE the
            # warm-up/preflight evals (tile-cache reuse makes those free,
            # but the accounting must not depend on that)
            dev_cold = _device_plane_delta(dev_cold0)
            assert len(rows) == N_INSTANCES, len(rows)
            # warm-up with caches on: builds the rolling tile / seeds the
            # result + eval caches
            api._exec_range_cached(EvalConfig(start=start, end=end0, **kw),
                                   q, end0)
            # preflight: two uncounted steady refreshes calibrate the
            # slow-refresh flight trigger for THIS host/leg — refreshes
            # >1.25x the calibrated floor freeze a cross-thread capture
            # mid-loop (an operator-set VM_SLOW_REFRESH_MS wins)
            from victoriametrics_tpu.utils import flightrec
            end = end0
            pre = []
            for _ in range(2):
                end += STEP
                ingest_fresh(end)
                t0 = time.perf_counter()
                api._exec_range_cached(
                    EvalConfig(start=end - duration, end=end, **kw), q, end)
                pre.append(time.perf_counter() - t0)
            if user_slow_refresh_ms is None:
                thresh_ms = max(min(pre) * 1.25e3, 25.0)
                os.environ["VM_SLOW_REFRESH_MS"] = str(thresh_ms)
            else:
                thresh_ms = user_slow_refresh_ms
            flight_id0 = flightrec.RECORDER.total()
            # steady-state: live ingest + window advance per refresh
            dev0 = _device_plane_totals()
            lat = []
            ph0 = _phase_totals()
            ing0 = _ingest_phase_totals()
            c0 = _cache_merge_totals()
            leg_costs = []
            for _ in range(REFRESHES):
                end += STEP
                start = end - duration
                ingest_fresh(end)
                tr = Tracer(True)
                ec_r = EvalConfig(start=start, end=end, **kw, tracer=tr)
                t0 = time.perf_counter()
                rows = api._exec_range_cached(ec_r, q, end)
                lat.append(time.perf_counter() - t0)
                leg_costs.append(ec_r.cost)
                assert len(rows) == N_INSTANCES, len(rows)
            traces[backend + "-steady"] = tr.to_dict()
            # snapshot the per-refresh phase split BEFORE the honesty
            # check: its cold full-window eval would otherwise pollute
            # the steady-state attribution
            phase_lbl = _phase_label(ph0, _phase_totals(), REFRESHES)
            ing_lbl = _ingest_phase_label(ing0, _ingest_phase_totals(),
                                          REFRESHES)
            cache_stats = _cache_merge_delta(c0)
            # device-plane deltas too: the honesty check's cold eval
            # would otherwise count as steady-state upload traffic
            dev_steady = _device_plane_delta(dev0)
            # flight attribution BEFORE the honesty check: its cold eval
            # would flood the rings with full-window fetch spans
            flights[backend] = _leg_flight_summary(flight_id0, thresh_ms)
            cost_summary = _cost_leg_summary(leg_costs, lat)
            self_delta = _self_metrics_delta(selfm0,
                                             _self_metrics_totals())
            # honesty check: the served refresh must equal a cold
            # (nocache) evaluation of the same window — bit-for-bit on
            # the f64 host path, within the f32 tile bound on device
            cold_rows = exec_query(EvalConfig(start=start, end=end, **kw,
                                              disable_cache=True), q)
            f32 = engine is not None and engine.is_f32()
            rtol = 0.0 if engine is None else (1e-4 if f32 else 1e-12)
            _assert_rows_equal(rows, cold_rows, rtol=rtol)
            results[backend] = (float(np.median(lat)), cold_dt,
                                phase_lbl, ing_lbl, list(lat), cache_stats,
                                cost_summary, self_delta)
            if backend == "device":
                # the residency story in the artifact: a steady refresh
                # must ship tail columns, not the window (ISSUE 12)
                device_plane = {
                    "cold_uploaded_bytes": dev_cold["uploaded_bytes"],
                    "steady_uploaded_bytes": dev_steady["uploaded_bytes"],
                    "steady_uploaded_per_refresh":
                        dev_steady["uploaded_bytes"] // max(REFRESHES, 1),
                    "steady_downloaded_bytes":
                        dev_steady["downloaded_bytes"],
                    "window_hits": dev_steady["window_hits"],
                    "window_compactions": dev_steady["window_compactions"],
                    "upload_ratio": round(
                        dev_steady["uploaded_bytes"] / max(REFRESHES, 1) /
                        max(dev_cold["uploaded_bytes"], 1), 5),
                }
            end0 = end  # the next backend continues on the grown storage

        backend, (warm_dt, cold_dt, phase_lbl, ing_lbl, lat,
                  cache_stats, cost_summary, _) = min(
            results.items(), key=lambda kv: kv[1][0])
        rate = samples / warm_dt
        # the refresh-latency DISTRIBUTION, not just p50: ROADMAP item 1's
        # variance hunt needs p99 and the raw list in the artifact
        p99_dt = float(np.percentile(lat, 99))
        from victoriametrics_tpu import native as native_mod
        from victoriametrics_tpu.utils import workpool
        n_workers = workpool.POOL.workers()
        assemble_mode = ("native" if native_mod.assemble_enabled()
                         else "python")
        with open("bench_trace.json", "w") as f:
            json.dump(traces, f, indent=1)
        baseline = 1e8  # single-core reference scan rate (see docstring)
        # honest backend accounting: the headline backend, with the
        # device label ("tpu-float32" etc.)
        backend_field = (backend_label if backend == "device"
                         else f"host-batch ({backend_label})")
        print(json.dumps({
            "metric": (f"steady-state rolling-window sum by(rate) serving, "
                       f"{N_SERIES}x{N_SAMPLES} counters, live ingest, via "
                       f"storage+index+decode+{backend} (cold "
                       f"{samples / cold_dt / 1e6:.0f}M/s, refresh p50 "
                       f"{warm_dt * 1e3:.0f}ms p99 {p99_dt * 1e3:.0f}ms, "
                       f"ingest "
                       f"{ingest_rate / 1e3:.0f}k rows/s, "
                       f"{n_workers} fetch workers, "
                       f"{workpool.configured_shards()} ingest shards, "
                       f"assemble={assemble_mode}, "
                       f"phases {phase_lbl}, "
                       f"ingest phases {ing_lbl})"),
            "value": round(rate),
            "unit": "samples/sec",
            "vs_baseline": round(rate / baseline, 2),
            "backend": backend_field,
            "refresh_p50_ms": round(warm_dt * 1e3, 2),
            "refresh_p99_ms": round(p99_dt * 1e3, 2),
            "refresh_ms": [round(x * 1e3, 2) for x in lat],
            "cache": cache_stats,
            # per-refresh cost attribution from the CostTracker plane
            # (profiler + accounting were ON for the whole run)
            "cost": cost_summary,
            "profiler": {
                "samples": profiler.PROFILER.snapshot()["samples"],
                "hz": profiler.configured_hz(),
            },
            # per-leg cold/steady timings: the device leg's numbers stay
            # visible even when the host leg wins the headline
            "legs": {b: {"refresh_p50_ms": round(r[0] * 1e3, 2),
                         "cold_s": round(r[1], 2),
                         "cost": r[6],
                         # the observability plane's own view of the leg
                         "self_metrics": r[7]}
                     for b, r in results.items()},
            "device_plane": device_plane,
            "flight": flights,
            "device": device_info,
            # end-of-run verdict from the self-monitoring plane (one
            # final scrape + eval round so it reflects the full run)
            "self_monitoring": _bench_health(scraper, plane_api,
                                             plane_engine, s),
        }))
    finally:
        try:
            if scraper is not None:
                # before s.close(): a late scrape must not write into a
                # closed storage
                scraper.stop()
        except Exception:
            pass
        try:
            s.close()
        except Exception:
            pass
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_health(scraper, plane_api, plane_engine, storage) -> dict:
    """One final scrape + eval round, then the health verdict — the
    artifact carries the plane's own view of the whole run."""
    from victoriametrics_tpu.query import sloplane
    if scraper is None:
        return {"disabled": "VM_SELF_SCRAPE_INTERVAL=0 (plane-overhead "
                            "A/B leg)"}
    try:
        scraper.scrape_once()
        plane_engine.maybe_eval(force=True)
        h = sloplane.local_health(storage=storage, engine=plane_engine,
                                  role="bench")
        return {
            "interval_s": scraper.interval_s,
            "scrapes": int(_self_metrics_totals().get(
                "vm_selfscrape_scrapes_total", 0)),
            "slo_eval_rounds": plane_engine.eval_rounds,
            "slo_exprs_per_round": plane_engine.exprs_last_round,
            "verdict": h["verdict"],
            "reasons": h["reasons"],
            "firing": [name for name, _ in plane_engine.firing()],
        }
    except Exception as e:  # noqa: BLE001 — artifact must still ship
        return {"error": str(e)}


FLEET_PANELS = (
    "sum by (instance)(rate(http_requests_total[5m]))",
    "sum by (job)(rate(http_requests_total[5m]))",
    "max by (instance)(rate(http_requests_total[5m]))",
    "count by (job)(rate(http_requests_total[5m]))",
)
FLEET_SUBS = 10          # subscribers PER PANEL (dashboards watching it)
FLEET_INTERVALS = 6


def fleet_main() -> None:
    """``--scenario=fleet``: N subscribers x M shared-selector panels
    served through the materialized-stream plane (query/matstream) —
    the first entry of ROADMAP item 5's bench matrix and ISSUE 14's
    acceptance artifact (BENCH_r11).

    Ingest the dashboard scenario's store (8192 counters x 1440
    samples, columnar write path), then:

    - FLAT-SCAN PROOF: per-interval ``samples_scanned`` with 1 vs
      ``FLEET_SUBS`` subscribers per panel — storage reads per interval
      must be independent of subscriber count (the tier-1 guard's
      number, measured at bench scale);
    - THROUGHPUT: ``FLEET_INTERVALS`` live-ingest intervals serving
      ``FLEET_SUBS x len(FLEET_PANELS)`` subscriptions; aggregate rate
      counts the window every SUBSCRIBER's dashboard logically renders
      per interval (the fleet accounting: N dashboards served, one
      evaluation each per distinct expression) over the measured
      advance+fan-out wall time;
    - POLL BASELINE: the same interval served by one
      ``_exec_range_cached`` poll per subscription (the PR-7 sharing
      story without push) — the artifact reports both, so the push
      win is not conflated with the ring cache's;
    - ORACLE: each panel's reassembled client state equals a cold
      nocache evaluation, bit for bit.

    Host-only by design (the acceptance target names host-only
    aggregate throughput); profiler + cost accounting stay ON."""
    from victoriametrics_tpu import native
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.query import rollup_result_cache as rrc
    from victoriametrics_tpu.query.exec import exec_query
    from victoriametrics_tpu.query.matstream import StreamClient
    from victoriametrics_tpu.query.types import EvalConfig
    from victoriametrics_tpu.storage.storage import Storage
    from victoriametrics_tpu.utils import profiler

    profiler.ensure_started()
    tmp = tempfile.mkdtemp(prefix="vmtpu-fleet-")
    now_ms = int(time.time() * 1000)
    t_start = (now_ms - (N_SAMPLES - 1) * 15_000) // STEP * STEP
    rng = np.random.default_rng(0)
    try:
        s = Storage(tmp)
        base = np.arange(N_SAMPLES, dtype=np.int64) * 15_000 + t_start
        keys = [(f'http_requests_total{{idx="{i}",'
                 f'instance="host-{i % N_INSTANCES}",'
                 f'job="job-{i % 17}"}}').encode()
                for i in range(N_SERIES)]
        keybuf = b"".join(keys)
        klens = np.fromiter((len(k) for k in keys), np.int64, N_SERIES)
        koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
        last_val = np.zeros(N_SERIES)
        t0 = time.perf_counter()
        chunk = 256
        for i0 in range(0, N_SERIES, chunk):
            i1 = min(i0 + chunk, N_SERIES)
            ts2 = np.sort(base[None, :] + rng.integers(
                -JITTER_MS, JITTER_MS + 1, (i1 - i0, N_SAMPLES)), axis=1)
            vals2 = np.cumsum(rng.integers(0, 50, (i1 - i0, N_SAMPLES)),
                              axis=1).astype(np.float64)
            last_val[i0:i1] = vals2[:, -1]
            s.add_rows_columnar(native.ColumnarRows(
                keybuf, np.repeat(koffs[i0:i1], N_SAMPLES),
                np.repeat(klens[i0:i1], N_SAMPLES),
                ts2.reshape(-1), vals2.reshape(-1)))
        ingest_rate = N_SERIES * N_SAMPLES / (time.perf_counter() - t0)
        s.force_flush()
        s.force_merge()

        # step-aligned: subscribe() rounds the window up to a step
        # multiple, and the end-of-run oracle must evaluate the exact
        # grid the stream serves
        duration = ((N_SAMPLES - 1) * 15_000 - 300_000) // STEP * STEP
        window_samples = N_SERIES * ((duration + 600_000) // 15_000)
        end = t_start + -(-((N_SAMPLES - 1) * 15_000 + JITTER_MS)
                          // STEP) * STEP

        def ingest_fresh(end_ms: int) -> None:
            incr = rng.integers(0, 50, (N_SERIES, 4))
            vals2 = last_val[:, None] + np.cumsum(incr, axis=1)
            last_val[:] = vals2[:, -1]
            ts2 = (end_ms - STEP +
                   (np.arange(4, dtype=np.int64) + 1)[None, :] * 15_000 +
                   rng.integers(-JITTER_MS, JITTER_MS + 1, (N_SERIES, 4)))
            ts2.sort(axis=1)
            s.add_rows_columnar(native.ColumnarRows(
                keybuf, np.repeat(koffs, 4), np.repeat(klens, 4),
                ts2.reshape(-1),
                vals2.reshape(-1).astype(np.float64)))

        rrc.GLOBAL.reset()
        api = PrometheusAPI(s)

        def drain(subs_by_panel, now):
            """Every subscriber consumes frames until its reassembled
            window reaches the current interval (no-op for subscribers
            already there)."""
            target = (now // STEP) * STEP
            for subs in subs_by_panel:
                for sub, cli in subs:
                    while not (cli.window and cli.window[1] >= target):
                        f = sub.next_frame(timeout_s=5.0, now_ms=now)
                        if f is None:
                            raise RuntimeError("subscriber starved")
                        cli.apply(f)

        def new_subs(n_per_panel):
            return [[(api.matstreams.subscribe(q, STEP, duration),
                      StreamClient()) for _ in range(n_per_panel)]
                    for q in FLEET_PANELS]

        # ---- flat-scan proof: 1 subscriber per panel ----
        subs = new_subs(1)
        drain(subs, end)           # cold: one eval per panel
        streams = [subs[p][0][0].stream for p in range(len(FLEET_PANELS))]
        samples_1sub = []
        for r in range(2):
            end += STEP
            ingest_fresh(end)
            api.matstreams.advance_due(end)
            drain(subs, end)
            samples_1sub.append(sum(st.last_samples_scanned
                                    for st in streams))
        # fan out to FLEET_SUBS per panel (cold replays, no eval)
        evals0 = sum(st.evals for st in streams)
        for p, q in enumerate(FLEET_PANELS):
            subs[p].extend(
                (api.matstreams.subscribe(q, STEP, duration),
                 StreamClient()) for _ in range(FLEET_SUBS - 1))
        drain(subs, end)
        assert sum(st.evals for st in streams) == evals0, \
            "cold subscribes re-evaluated"
        samples_nsub = []
        for r in range(2):
            end += STEP
            ingest_fresh(end)
            api.matstreams.advance_due(end)
            drain(subs, end)
            samples_nsub.append(sum(st.last_samples_scanned
                                    for st in streams))

        # ---- throughput: FLEET_INTERVALS pushed intervals ----
        n_subscriptions = FLEET_SUBS * len(FLEET_PANELS)
        push_wall = []
        interval_samples = []
        for r in range(FLEET_INTERVALS):
            end += STEP
            ingest_fresh(end)
            t0 = time.perf_counter()
            api.matstreams.advance_due(end)
            drain(subs, end)
            push_wall.append(time.perf_counter() - t0)
            interval_samples.append(sum(st.last_samples_scanned
                                        for st in streams))
        # ---- poll baseline: the same interval, one cached poll per
        # subscription — canonical text, so the polls share the
        # STREAMS' warm ring entries (the strongest PR-7 baseline:
        # suffix merge once per panel, then pure full hits) ----
        canon = [api.matstreams.canonical(q) for q in FLEET_PANELS]
        poll_wall = []
        for r in range(3):
            end += STEP
            ingest_fresh(end)
            t0 = time.perf_counter()
            for q in canon:
                for _ in range(FLEET_SUBS):
                    api._exec_range_cached(
                        EvalConfig(start=end - duration, end=end,
                                   step=STEP, storage=s), q, end)
            if r > 0:  # first interval warms the poll path's entries
                poll_wall.append(time.perf_counter() - t0)

        # ---- oracle: every panel's pushed state == cold eval ----
        # (polls above advanced the shared ring entries past the last
        # pushed frame, so push one final interval first)
        end += STEP
        ingest_fresh(end)
        api.matstreams.advance_due(end)
        drain(subs, end)
        import math as _math
        for p, q in enumerate(FLEET_PANELS):
            ec = EvalConfig(start=end - duration, end=end, step=STEP,
                            storage=s, disable_cache=True)
            cold = exec_query(ec, q)
            grid = ec.timestamps() / 1e3
            from victoriametrics_tpu.query.format_value import fmt_value
            want = []
            for rr in cold:
                vals = [[float(t), fmt_value(v)]
                        for t, v in zip(grid, rr.values)
                        if not _math.isnan(v)]
                if vals:
                    want.append({"metric": rr.metric_name.to_dict(),
                                 "values": vals})
            want.sort(key=lambda e: json.dumps(e["metric"],
                                               sort_keys=True))
            for sub, cli in subs[p]:
                assert cli.result() == want, \
                    f"panel {p} pushed state diverged from cold eval"

        usage = api.matstreams.usage_rows()
        p50_push = float(np.median(push_wall))
        p50_poll = float(np.median(poll_wall))
        agg_rate = n_subscriptions * window_samples / p50_push
        baseline = 1e8
        med_1 = int(np.median(samples_1sub))
        med_n = int(np.median(samples_nsub))
        for subs_p in subs:
            for sub, _ in subs_p:
                sub.close()
        print(json.dumps({
            "metric": (
                f"fleet subscription push: {n_subscriptions} "
                f"subscriptions ({FLEET_SUBS} dashboards x "
                f"{len(FLEET_PANELS)} shared-selector panels), "
                f"{N_SERIES}x{N_SAMPLES} counters, live ingest, "
                f"served via materialized streams (one eval per "
                f"distinct expression per interval; aggregate rate "
                f"counts each subscriber's rendered window; ingest "
                f"{ingest_rate / 1e3:.0f}k rows/s; poll-loop baseline "
                f"= {FLEET_SUBS} cached query_range polls per panel)"),
            "value": round(agg_rate),
            "unit": "samples/sec",
            "vs_baseline": round(agg_rate / baseline, 2),
            "backend": "host-batch",
            "scenario": "fleet",
            "subscribers_per_panel": FLEET_SUBS,
            "panels": len(FLEET_PANELS),
            "streams": api.matstreams.stream_count(),
            "push_interval_ms": [round(x * 1e3, 2) for x in push_wall],
            "push_interval_p50_ms": round(p50_push * 1e3, 2),
            "poll_interval_ms": [round(x * 1e3, 2) for x in poll_wall],
            "poll_interval_p50_ms": round(p50_poll * 1e3, 2),
            "push_vs_poll_speedup": round(p50_poll / p50_push, 2),
            "storage_reads_flat": {
                "samples_per_interval_1sub": med_1,
                f"samples_per_interval_{FLEET_SUBS}sub": med_n,
                "flat": bool(med_n <= med_1 * 1.2),
            },
            "samples_scanned_per_interval": interval_samples,
            "per_stream_usage": usage,
            "profiler": {
                "samples": profiler.PROFILER.snapshot()["samples"],
                "hz": profiler.configured_hz(),
            },
        }))
        assert med_n <= med_1 * 1.2, (
            "storage reads per interval grew with subscribers")
    finally:
        try:
            s.close()
        except Exception:
            pass
        shutil.rmtree(tmp, ignore_errors=True)


FLEETD_SERIES = 4096       # 64 instances x 64 jobs, every pair distinct
FLEETD_INSTANCES = 64
FLEETD_JOBS = 64
FLEETD_SAMPLES = 240       # 1h @ 15s
FLEETD_SCRAPE = 15_000
FLEETD_DUR = 20 * STEP     # rendered window per subscription
FLEETD_WARM = 2            # adoption intervals before measurement starts
FLEETD_PANELS = (
    "sum by (instance)(rate(http_requests_total[5m]))",
    "sum by (job)(rate(http_requests_total[5m]))",
    "max by (instance)(rate(http_requests_total[5m]))",
    "count by (job)(rate(http_requests_total[5m]))",
)


def fleet_device_main() -> None:
    """``--scenario=fleet --device``: the MULTICHIP_r07 acceptance leg
    (ISSUE 19 / ROADMAP item 3) — fleet-batched device serving on the
    virtual 8-device mesh.

    ``FLEET_SUBS x len(FLEETD_PANELS)`` = 40 subscriptions over a corpus
    shaped so every panel lands in ONE fleet bucket (4096 counters =
    64 instances x 64 jobs, so ``by (instance)`` and ``by (job)`` both
    reduce to G=64 and share the G rung; same selector -> same S=4096
    rung; same duration/step -> same T rung).  The run then proves, per
    measured interval: exactly ONE fused mesh launch serves all four
    member streams, zero backend recompiles (<= 2 XLA compiles per
    bucket over the whole run), the rows-share cost split of the shared
    launch sums to the launch wall across the usage rows, and the
    served windows match BOTH oracles at rtol=1e-12 — a cold host
    evaluation and a deterministic ``VM_DEVICE_FLEET=0`` per-stream
    replay of the same sequence.  A two-subprocess probe (same
    machinery as the tools/lint.sh compile-cache smoke) shows a warm
    restart compiles 0 kernels with ``JAX_COMPILATION_CACHE_DIR`` set."""
    from victoriametrics_tpu import native
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.query import rollup_result_cache as rrc
    from victoriametrics_tpu.query.exec import exec_query
    from victoriametrics_tpu.query.matstream import StreamClient
    from victoriametrics_tpu.query.types import EvalConfig
    from victoriametrics_tpu.utils import flightrec, profiler

    from __graft_entry__ import _provision_devices
    devices = _provision_devices(8)
    import jax
    jax.config.update("jax_enable_x64", True)
    from victoriametrics_tpu.parallel.mesh import make_mesh
    from victoriametrics_tpu.query.tpu_engine import (TPUEngine,
                                                      backend_compiles)
    from victoriametrics_tpu.storage.storage import Storage

    profiler.ensure_started()
    mesh = make_mesh(n_series=8, n_time=1, devices=devices[:8])
    now_ms = int(time.time() * 1000)
    t0 = (now_ms - (FLEETD_SAMPLES - 1) * FLEETD_SCRAPE) // STEP * STEP
    end0 = t0 + ((FLEETD_SAMPLES - 1) * FLEETD_SCRAPE // STEP + 1) * STEP
    keys = [(f'http_requests_total{{instance="host-{i // FLEETD_JOBS}",'
             f'job="job-{i % FLEETD_JOBS}"}}').encode()
            for i in range(FLEETD_SERIES)]
    keybuf = b"".join(keys)
    klens = np.fromiter((len(k) for k in keys), np.int64, FLEETD_SERIES)
    koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
    tmp = tempfile.mkdtemp(prefix="vmtpu-fleetdev-")

    def _rows(entries):
        return {json.dumps(e["metric"], sort_keys=True):
                np.array([[float(t), float(v)] for t, v in e["values"]])
                for e in entries}

    def _max_rel(got, want, ctx):
        """assert_allclose at the rtol=1e-12 contract AND report the
        actual worst relative error for the artifact."""
        assert set(got) == set(want), (ctx, sorted(set(got) ^ set(want))[:4])
        worst = 0.0
        for k in sorted(got):
            g, w = got[k], want[k]
            assert g.shape == w.shape, (ctx, k, g.shape, w.shape)
            np.testing.assert_allclose(g, w, rtol=1e-12, atol=0,
                                       err_msg=f"{ctx} {k}")
            denom = np.maximum(np.abs(w), 1e-300)
            worst = max(worst, float(np.max(np.abs(g - w) / denom))
                        if g.size else 0.0)
        return worst

    def leg(sub_dir, fleet_on, n_per_panel, n_intervals):
        """One deterministic serving sequence over a fresh storage (same
        t0 + same rng seed => identical rows leg-to-leg).  Returns the
        per-interval reassembled windows plus the fleet counters and, on
        the fleet leg, the measured interval walls / cost split / cold
        oracle."""
        rng = np.random.default_rng(0)
        last = np.zeros(FLEETD_SERIES)
        prev_env = os.environ.pop("VM_DEVICE_FLEET", None)
        if not fleet_on:
            os.environ["VM_DEVICE_FLEET"] = "0"
        s = Storage(os.path.join(tmp, sub_dir))
        orig_rec = flightrec.rec
        try:
            base = (np.arange(FLEETD_SAMPLES, dtype=np.int64)
                    * FLEETD_SCRAPE + t0)
            chunk = 512
            for i0 in range(0, FLEETD_SERIES, chunk):
                i1 = min(i0 + chunk, FLEETD_SERIES)
                vals2 = np.cumsum(
                    rng.integers(0, 50, (i1 - i0, FLEETD_SAMPLES)),
                    axis=1).astype(np.float64)
                last[i0:i1] = vals2[:, -1]
                ts2 = np.ascontiguousarray(np.broadcast_to(
                    base, (i1 - i0, FLEETD_SAMPLES)))
                s.add_rows_columnar(native.ColumnarRows(
                    keybuf, np.repeat(koffs[i0:i1], FLEETD_SAMPLES),
                    np.repeat(klens[i0:i1], FLEETD_SAMPLES),
                    ts2.reshape(-1), vals2.reshape(-1)))
            s.force_flush()
            s.force_merge()

            def ingest_fresh(end_ms):
                incr = rng.integers(0, 50, (FLEETD_SERIES, 4))
                vals2 = last[:, None] + np.cumsum(incr, axis=1)
                last[:] = vals2[:, -1]
                ts2 = np.broadcast_to(
                    end_ms - STEP + (np.arange(4, dtype=np.int64) + 1)
                    * FLEETD_SCRAPE, (FLEETD_SERIES, 4))
                s.add_rows_columnar(native.ColumnarRows(
                    keybuf, np.repeat(koffs, 4), np.repeat(klens, 4),
                    np.ascontiguousarray(ts2).reshape(-1),
                    vals2.reshape(-1).astype(np.float64)))

            rrc.GLOBAL.reset()
            engine = TPUEngine(min_series=4, mesh=mesh)
            api = PrometheusAPI(s, engine)
            subs = [[(api.matstreams.subscribe(q, STEP, FLEETD_DUR),
                      StreamClient()) for _ in range(n_per_panel)]
                    for q in FLEETD_PANELS]

            def drain(now):
                target = now // STEP * STEP
                for panel in subs:
                    for sub, cli in panel:
                        while not (cli.window and cli.window[1] >= target):
                            f = sub.next_frame(timeout_s=60.0, now_ms=now)
                            if f is None:
                                raise RuntimeError("subscriber starved")
                            cli.apply(f)

            drain(end0)
            plane = engine.fleet()
            walls = []

            def spy(name, t_s, dur, *rest, **kw):
                if name == "device:fleet_launch":
                    walls.append(dur)
                return orig_rec(name, t_s, dur, *rest, **kw)

            flightrec.rec = spy

            def exec_ms():
                return sum(ms.usage_row().get("deviceExecMs", 0.0)
                           for ms in api.matstreams.streams())

            out = {"results": [], "push_wall": [], "intervals": [],
                   "cost": []}
            end = end0
            for r in range(n_intervals):
                end += STEP
                ingest_fresh(end)
                walls.clear()
                st0 = plane.stats()
                e0 = exec_ms()
                tw = time.perf_counter()
                api.matstreams.advance_due(end)
                drain(end)
                wall = time.perf_counter() - tw
                st1 = plane.stats()
                out["results"].append(
                    {q: _rows(panel[0][1].result())
                     for q, panel in zip(FLEETD_PANELS, subs)})
                for q, panel in zip(FLEETD_PANELS, subs):
                    head = panel[0][1].result()
                    for _, cli in panel[1:]:
                        assert cli.result() == head, (
                            f"fan-out subscribers of {q!r} diverged")
                if not (fleet_on and r >= FLEETD_WARM):
                    continue
                out["push_wall"].append(wall)
                d = {k: st1[k] - st0[k]
                     for k in ("launches", "served", "compiles")}
                assert st1["buckets"] == 1, (
                    f"panels split across {st1['buckets']} buckets — the "
                    "64x64 corpus no longer shares one G/S/T rung")
                assert st1["members"] == len(FLEETD_PANELS), st1
                assert d["launches"] == 1, (
                    f"interval {r}: {d['launches']} launches for 1 bucket "
                    "— fleet batching regressed to per-stream programs")
                assert d["served"] == len(FLEETD_PANELS), (r, d)
                assert d["compiles"] == 0, (
                    f"interval {r}: warm interval paid a backend compile")
                out["intervals"].append(d)
                billed = exec_ms() - e0
                launch_ms = sum(walls) * 1e3
                assert launch_ms > 0, "no fleet launch recorded"
                assert abs(billed - launch_ms) < \
                    0.05 + 0.002 * len(FLEETD_PANELS), (
                    f"interval {r}: usage rows billed {billed:.3f}ms for "
                    f"{launch_ms:.3f}ms of shared launches")
                out["cost"].append({"billed_ms": round(billed, 3),
                                    "launch_ms": round(launch_ms, 3)})
            out["stats"] = plane.stats()
            out["usage"] = api.matstreams.usage_rows()
            if fleet_on:
                # cold host oracle at the final interval
                import math as _math

                from victoriametrics_tpu.query.format_value import fmt_value
                worst = 0.0
                for q, panel in zip(FLEETD_PANELS, subs):
                    ec = EvalConfig(start=end - FLEETD_DUR, end=end,
                                    step=STEP, storage=s,
                                    disable_cache=True)
                    grid = ec.timestamps() / 1e3
                    want = {}
                    for rr in exec_query(ec, q):
                        vals = np.array(
                            [[float(t), float(fmt_value(v))]
                             for t, v in zip(grid, rr.values)
                             if not _math.isnan(v)])
                        if len(vals):
                            want[json.dumps(rr.metric_name.to_dict(),
                                            sort_keys=True)] = vals
                    worst = max(worst, _max_rel(
                        _rows(panel[0][1].result()), want,
                        f"cold oracle {q!r}"))
                out["cold_max_rel"] = worst
            for panel in subs:
                for sub, _ in panel:
                    sub.close()
            return out
        finally:
            flightrec.rec = orig_rec
            os.environ.pop("VM_DEVICE_FLEET", None)
            if prev_env is not None:
                os.environ["VM_DEVICE_FLEET"] = prev_env
            try:
                s.close()
            except Exception:
                pass

    try:
        t_leg = time.perf_counter()
        fleet = leg("fleet-on", True, FLEET_SUBS,
                    FLEETD_WARM + FLEET_INTERVALS)
        fleet_wall_s = time.perf_counter() - t_leg
        compiles_proc = backend_compiles()
        t_leg = time.perf_counter()
        off = leg("fleet-off", False, 1, FLEETD_WARM + 4)
        off_wall_s = time.perf_counter() - t_leg
        assert off["stats"]["launches"] == 0, (
            "VM_DEVICE_FLEET=0 still launched fleet programs")
        # batched == per-stream across every overlapping interval of the
        # deterministic replay
        ps_max_rel = 0.0
        for r, (g, w) in enumerate(zip(fleet["results"], off["results"])):
            for q in FLEETD_PANELS:
                ps_max_rel = max(ps_max_rel, _max_rel(
                    g[q], w[q], f"per-stream oracle interval {r} {q!r}"))

        # warm-restart probe: two cold subprocesses sharing one
        # JAX_COMPILATION_CACHE_DIR — the second must compile nothing
        from victoriametrics_tpu.devtools.compile_cache_smoke import _spawn
        cache_dir = tempfile.mkdtemp(prefix="vmtpu-fleetdev-ccache-")
        try:
            cold = _spawn(cache_dir)
            warm = _spawn(cache_dir)
            assert warm["compiles"] == 0, (
                f"warm restart recompiled {warm['compiles']} kernels "
                "with the persistent cache enabled")
            warm_restart = {
                "cold_compiles": cold["compiles"],
                "warm_compiles": warm["compiles"],
                "warm_cache_hits": warm["hits"],
            }
        finally:
            shutil.rmtree(cache_dir, ignore_errors=True)

        n_subscriptions = FLEET_SUBS * len(FLEETD_PANELS)
        window_samples = FLEETD_SERIES * ((FLEETD_DUR + 600_000)
                                          // FLEETD_SCRAPE)
        p50_push = float(np.median(fleet["push_wall"]))
        agg_rate = n_subscriptions * window_samples / p50_push
        st = fleet["stats"]
        assert st["compiles"] <= 2 * st["buckets"], (
            f"{st['compiles']} backend compiles for {st['buckets']} "
            "bucket(s) — the <=2-per-bucket acceptance bound broke")
        print(json.dumps({
            "metric": (
                f"fleet-batched device serving: {n_subscriptions} "
                f"subscriptions ({FLEET_SUBS} dashboards x "
                f"{len(FLEETD_PANELS)} shared-selector panels) over "
                f"{FLEETD_SERIES} counters ({FLEETD_INSTANCES} instances "
                f"x {FLEETD_JOBS} jobs, so by(instance)/by(job) share "
                f"the G=64 rung) on the virtual 8-device mesh — ONE "
                f"fused launch per interval serves the whole fleet, "
                f"{st['compiles']} backend compile(s) total, parity at "
                f"rtol=1e-12 with both the cold host oracle and the "
                f"VM_DEVICE_FLEET=0 per-stream replay"),
            "artifact": "MULTICHIP_r07",
            "value": round(agg_rate),
            "unit": "samples/sec",
            "backend": "cpu-device-float64",
            "scenario": "fleet-device",
            "n_devices": len(devices),
            "subscriptions": n_subscriptions,
            "subscribers_per_panel": FLEET_SUBS,
            "panels": len(FLEETD_PANELS),
            "series": FLEETD_SERIES,
            "groups_per_panel": FLEETD_INSTANCES,
            "push_interval_ms": [round(x * 1e3, 2)
                                 for x in fleet["push_wall"]],
            "push_interval_p50_ms": round(p50_push * 1e3, 2),
            "fleet": {
                "buckets": st["buckets"],
                "members": st["members"],
                "adoptions": st["adoptions"],
                "evictions": st["evictions"],
                "launches_total": st["launches"],
                "served_total": st["served"],
                "bucket_compiles_total": st["compiles"],
                "per_measured_interval": fleet["intervals"],
            },
            "cost_split": {
                "per_interval": fleet["cost"],
                "max_abs_gap_ms": round(max(
                    abs(c["billed_ms"] - c["launch_ms"])
                    for c in fleet["cost"]), 3),
            },
            "oracles": {
                "rtol": 1e-12,
                "served_vs_cold_max_rel": fleet["cold_max_rel"],
                "served_vs_per_stream_max_rel": ps_max_rel,
                "per_stream_leg": {
                    "intervals_compared": min(len(fleet["results"]),
                                              len(off["results"])),
                    "fleet_launches": off["stats"]["launches"],
                    "wall_s": round(off_wall_s, 1),
                },
            },
            "warm_restart": warm_restart,
            "process_backend_compiles_after_fleet_leg": compiles_proc,
            "fleet_leg_wall_s": round(fleet_wall_s, 1),
            "per_stream_usage": fleet["usage"],
            "reference": {
                "BENCH_r11_host_fleet": {
                    "samples_per_sec": 956106707,
                    "push_interval_p50_ms": 499.01,
                },
                "BENCH_r12_device_leg": {
                    "refresh_p50_ms": 1406.85,
                    "device_execute_ms_per_capture": 1332.14,
                    "device_compile_ms_per_capture": 2825.11,
                    "note": ("r12 paid one compile and one launch per "
                             "query shape per process; this run pays "
                             "one fused launch per interval for the "
                             "whole fleet and restarts warm"),
                },
            },
            "profiler": {
                "samples": profiler.PROFILER.snapshot()["samples"],
                "hz": profiler.configured_hz(),
            },
        }))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


CL_SERIES = int(os.environ.get("VM_BENCH_CLUSTER_SERIES", "4096"))
CL_SAMPLES = int(os.environ.get("VM_BENCH_CLUSTER_SAMPLES", "360"))
CL_READS = 5


def _spawn_vmstorage(base_dir: str, tag: str):
    """One real vmstorage OS process on loopback ports; returns
    (Popen, http_port, node_spec)."""
    import socket
    import subprocess
    import urllib.request

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    hp, ip_, sp = free_port(), free_port(), free_port()
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "victoriametrics_tpu.apps.vmstorage",
         f"-storageDataPath={base_dir}/{tag}",
         # the cluster corpus sits at a literal 2025-07-28: a stated
         # retention, so that no merge drops it whatever today's date is
         "-retentionPeriod=100y",
         f"-httpListenAddr=127.0.0.1:{hp}",
         f"-vminsertAddr=127.0.0.1:{ip_}",
         f"-vmselectAddr=127.0.0.1:{sp}"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{hp}/health", timeout=1):
                break
        except OSError:
            if proc.poll() is not None:
                raise RuntimeError(f"vmstorage {tag} died at startup")
            time.sleep(0.1)
    else:
        raise TimeoutError(f"vmstorage {tag} never became ready")
    return proc, hp, f"127.0.0.1:{ip_}:{sp}"


def _cluster_corpus():
    """(keybuf, koffs, klens, per-chunk ingest fn inputs) for the
    cluster corpus: CL_SERIES counters x CL_SAMPLES scrapes."""
    rng = np.random.default_rng(12)
    t0 = 1_753_700_000_000
    keys = [(f'cbench{{idx="{i}",instance="h{i % 64}",'
             f'job="j{i % 7}"}}').encode() for i in range(CL_SERIES)]
    klens = np.fromiter((len(k) for k in keys), np.int64, CL_SERIES)
    koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
    base = np.arange(CL_SAMPLES, dtype=np.int64) * 15_000 + t0
    vals = np.cumsum(rng.integers(0, 40, (CL_SERIES, CL_SAMPLES)),
                     axis=1).astype(np.float64)
    return b"".join(keys), koffs, klens, base, vals, t0


def _cluster_ingest(cluster, keybuf, koffs, klens, base, vals,
                    chunk=512):
    from victoriametrics_tpu import native
    t0 = time.perf_counter()
    for i0 in range(0, CL_SERIES, chunk):
        i1 = min(i0 + chunk, CL_SERIES)
        n = i1 - i0
        cluster.add_rows_columnar(native.ColumnarRows(
            keybuf, np.repeat(koffs[i0:i1], CL_SAMPLES),
            np.repeat(klens[i0:i1], CL_SAMPLES),
            np.tile(base, n),
            vals[i0:i1].reshape(-1)))
    return CL_SERIES * CL_SAMPLES / (time.perf_counter() - t0)


def cluster_main() -> None:
    """``--scenario=cluster`` (ISSUE 15 / ROADMAP item 3 acceptance
    artifact, CLUSTER_r12): real vmstorage OS processes behind the
    in-process ClusterStorage router (the vmselect/vminsert role).

    Sections, each with its invariant asserted in-run:

    - SCALING 1 -> 4 nodes: the same corpus served by 1 and by 4
      vmstorage processes.  ``work_efficiency`` (how evenly the ring
      spreads per-node scan work: total/(N x max-node share)) is the
      scaling claim on an adequately-cored box; measured wall times on
      THIS box ship alongside (on 1 shared core, wall cannot improve).
    - RF=2 RING FILTERING: bytes over the read fan-out with
      ring-ownership filtering on vs off (off reads every replica
      twice), plus bit-equality of both results.
    - REROUTE: with one of the RF=2 nodes down, the full vector is
      byte-identical to the healthy read (vm_reroute_reads_total
      ticking, not partial).
    - REBALANCE UNDER LIVE INGEST: a node joins mid-ingest and
      rebalance_to moves real parts while writes continue — zero write
      errors, exact final counts/sums, byte-exact reads.
    - TENANT QoS THROUGH REROUTE: a quota-capped tenant storms while a
      node is down; the other tenant's p99 stays within 3x unloaded.
    """
    import threading
    import urllib.request

    from victoriametrics_tpu import native
    from victoriametrics_tpu.parallel import ringfilter
    from victoriametrics_tpu.parallel.cluster_api import (
        ClusterStorage, StorageNodeClient, parse_node_spec)
    from victoriametrics_tpu.storage.tag_filters import TagFilter
    from victoriametrics_tpu.utils import costacc
    from victoriametrics_tpu.utils import metrics as metricslib

    os.environ.setdefault("VM_MIGRATE_GRACE_MS", "300")
    tmp = tempfile.mkdtemp(prefix="vmtpu-cluster-")
    procs = []
    out: dict = {"scenario": "cluster", "series": CL_SERIES,
                 "samples_per_series": CL_SAMPLES,
                 "cores": os.cpu_count()}
    keybuf, koffs, klens, base, vals, t0 = _cluster_corpus()
    t_lo, t_hi = int(base[0]), int(base[-1]) + 1
    f = [TagFilter(b"", b"cbench")]

    def spawn(tag):
        p, hp, spec = _spawn_vmstorage(tmp, tag)
        procs.append(p)
        return hp, spec

    def read_wall(cluster):
        walls = []
        cols = None
        for _ in range(CL_READS):
            w0 = time.perf_counter()
            cols = cluster.search_columns(f, t_lo, t_hi)
            walls.append(time.perf_counter() - w0)
        assert cols.n_series == CL_SERIES
        assert cols.n_samples == CL_SERIES * CL_SAMPLES
        return float(np.median(walls)), cols

    try:
        # ---- scaling: 1 node vs 4 nodes -------------------------------
        _, spec1 = spawn("n1")
        c1 = ClusterStorage([StorageNodeClient(*parse_node_spec(spec1))])
        rate1 = _cluster_ingest(c1, keybuf, koffs, klens, base, vals)
        wall1, cols1 = read_wall(c1)

        specs4 = [spawn(f"m{i}")[1] for i in range(4)]
        c4 = ClusterStorage([StorageNodeClient(*parse_node_spec(s))
                             for s in specs4])
        rate4 = _cluster_ingest(c4, keybuf, koffs, klens, base, vals)
        wall4, cols4 = read_wall(c4)
        assert cols4.raw_names == cols1.raw_names
        assert np.array_equal(cols4.vals, cols1.vals), \
            "4-node read diverged from 1-node read"
        shares = [n.series_count() for n in c4.nodes]
        total = sum(shares)
        work_eff = total / (len(shares) * max(shares))
        out["scaling"] = {
            "read_wall_1node_ms": round(wall1 * 1e3, 1),
            "read_wall_4node_ms": round(wall4 * 1e3, 1),
            "wall_speedup_1_to_4": round(wall1 / wall4, 2),
            "ingest_rows_per_s_1node": round(rate1),
            "ingest_rows_per_s_4node": round(rate4),
            "per_node_series": shares,
            "work_efficiency_1_to_4": round(work_eff, 3),
            "note": ("work_efficiency = total/(N*max node share): the "
                     "ring's per-node scan-work split, i.e. read "
                     "scaling on a box with >= N cores; this box has "
                     f"{os.cpu_count()} core(s), so wall times are "
                     "CPU-serialized"),
        }
        assert work_eff >= 0.7, f"scaling efficiency {work_eff} < 0.7"
        c1.close()

        # ---- rf=2 ring filtering: read amplification ------------------
        # (these nodes also host the QoS-through-reroute section, so
        # tenant 1 is quota-capped on the storage side)
        os.environ["VM_TENANT_QUOTAS"] = "1:0=1:100:low"
        try:
            specs2 = [spawn(f"r{i}")[1] for i in range(2)]
        finally:
            del os.environ["VM_TENANT_QUOTAS"]
        c2 = ClusterStorage([StorageNodeClient(*parse_node_spec(s))
                             for s in specs2], replication_factor=2)
        _cluster_ingest(c2, keybuf, koffs, klens, base, vals)

        def fanout_bytes():
            tr = costacc.CostTracker()
            prev = costacc.set_current(tr)
            try:
                cols = c2.search_columns(f, t_lo, t_hi)
            finally:
                costacc.set_current(prev)
            return tr.rpc_bytes, cols

        by_on, cols_on = fanout_bytes()
        os.environ["VM_RING_FILTER"] = "0"
        try:
            by_off, cols_off = fanout_bytes()
        finally:
            del os.environ["VM_RING_FILTER"]
        assert cols_on.raw_names == cols_off.raw_names
        assert np.array_equal(cols_on.vals, cols_off.vals)
        out["rf2_ring_filter"] = {
            "fanout_rpc_bytes_ring_on": int(by_on),
            "fanout_rpc_bytes_ring_off": int(by_off),
            "read_amplification_saved": round(by_off / by_on, 2),
        }
        assert by_off > by_on * 1.6, \
            "ring filtering did not cut replica read amplification"

        # ---- reroute: down node, complete results ---------------------
        rr = metricslib.REGISTRY.counter("vm_reroute_reads_total")
        r0 = rr.get()
        c2.nodes[0].mark_down(3600.0)
        c2.reset_partial()
        w0 = time.perf_counter()
        cols_rr = c2.search_columns(f, t_lo, t_hi)
        reroute_wall = time.perf_counter() - w0
        assert cols_rr.raw_names == cols_on.raw_names
        assert np.array_equal(cols_rr.vals, cols_on.vals), \
            "rerouted read not byte-identical"
        assert not c2.last_partial, "rerouted read flagged partial"
        out["reroute"] = {
            "complete": True,
            "partial": bool(c2.last_partial),
            "read_wall_ms": round(reroute_wall * 1e3, 1),
            "vm_reroute_reads_total_delta": int(rr.get() - r0),
        }
        assert rr.get() > r0

        # ---- tenant QoS through the reroute path ----------------------
        def q(tenant, i):
            w0 = time.perf_counter()
            c2.search_columns(f, t_lo, t_lo + 90_000, tenant=tenant)
            return time.perf_counter() - w0

        unloaded = sorted(q((2, 0), i) for i in range(15))
        stop = threading.Event()
        sheds = [0]
        t1_served = [0]

        def storm():
            while not stop.is_set():
                try:
                    q((1, 0), 0)
                    t1_served[0] += 1
                except Exception:
                    sheds[0] += 1  # quota shed (429-equivalent)

        storms = [threading.Thread(target=storm) for _ in range(2)]
        for th in storms:
            th.start()
        time.sleep(0.2)
        try:
            loaded = sorted(q((2, 0), i) for i in range(15))
        finally:
            stop.set()
            for th in storms:
                th.join(timeout=10)
        p99u = unloaded[-1]
        p99l = loaded[-1]
        out["tenant_qos_through_reroute"] = {
            "tenant1_quota": "1 concurrent / 100ms queue (low prio)",
            "tenant1_served": t1_served[0],
            "tenant1_shed": sheds[0],
            "tenant2_p99_unloaded_ms": round(p99u * 1e3, 1),
            "tenant2_p99_loaded_ms": round(p99l * 1e3, 1),
            "isolation_ratio": round(p99l / p99u, 2),
        }
        assert p99l <= 3 * p99u, \
            f"tenant-2 isolation broke through reroute: {p99l / p99u:.1f}x"
        c2.nodes[0].down_until = 0.0
        c2.close()

        # ---- rebalance under live ingest ------------------------------
        c4b = c4
        write_errors = []
        stop = threading.Event()
        wrote = [0]

        def writer():
            b = 0
            while not stop.is_set():
                rows = [({"__name__": "live", "series": str(i)},
                         t_hi + b * 15_000, float(i + b))
                        for i in range(128)]
                try:
                    c4b.add_rows(rows)
                    wrote[0] = b + 1
                except Exception as e:
                    write_errors.append(str(e))
                b += 1
                time.sleep(0.01)

        wt = threading.Thread(target=writer)
        wt.start()
        time.sleep(0.3)
        _, spec5 = spawn("n5")
        mig0 = metricslib.REGISTRY.counter(
            "vm_parts_migrated_total").get()
        c4b.add_node(spec5)
        stat = c4b.rebalance_to(parse_node_spec(spec5)[0] + ":" +
                                str(parse_node_spec(spec5)[1]))
        time.sleep(0.3)
        stop.set()
        wt.join(timeout=30)
        n_batches = wrote[0]
        got = c4b.search_columns(
            [TagFilter(b"", b"live")], t_hi,
            t_hi + (n_batches + 1) * 15_000)
        assert not write_errors, write_errors[:3]
        assert got.n_series == 128
        # zero dropped acked writes: every acked batch's samples present
        assert int(got.counts.sum()) == 128 * n_batches, \
            (int(got.counts.sum()), 128 * n_batches)
        # the original corpus still reads byte-exact post-rebalance
        wall5, cols5 = read_wall(c4b)
        assert cols5.raw_names == cols1.raw_names
        assert np.array_equal(cols5.vals, cols1.vals), \
            "post-rebalance read diverged"
        out["rebalance_under_ingest"] = {
            "parts_moved": stat["parts"],
            "bytes_moved": stat["bytes"],
            "vm_parts_migrated_total_delta": int(
                metricslib.REGISTRY.counter(
                    "vm_parts_migrated_total").get() - mig0),
            "acked_write_batches": n_batches,
            "write_errors": 0,
            "dropped_acked_writes": 0,
            "post_rebalance_read_wall_ms": round(wall5 * 1e3, 1),
            "byte_exact": True,
        }
        c4b.close()
        out["metric"] = (
            f"elastic cluster serving: {CL_SERIES}x{CL_SAMPLES} corpus "
            f"over real vmstorage processes — ring work-split "
            f"efficiency {out['scaling']['work_efficiency_1_to_4']} "
            f"(1->4 nodes), rf2 ring filtering saves "
            f"{out['rf2_ring_filter']['read_amplification_saved']}x "
            f"read bytes, down-shard reroute complete, join+rebalance "
            f"under live ingest with 0 dropped acked writes "
            f"({stat['parts']} parts / {stat['bytes']} bytes moved)")
        print(json.dumps(out))
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# r13 multi-workload matrix: churn / backfill / qstorm / longrange.
#
# Four workload shapes the single dashboard loop cannot see, each a
# first-class scenario emitting its own BENCH_r13_<scenario>.json with
# the standard attribution splits (per-phase fetch time, result-cache
# merge handling, per-refresh CostTracker, flight-recorder captures):
#
#   churn      every refresh retires part of the live fleet and births
#              replacement identities while part writes run CONCURRENT
#              with serving — merges must DEFER to refreshes
#              (vm_merge_gate_yields_total ticks) and the latency
#              distribution must stay flat (p99 <= 2x p50);
#   backfill   historical chunks land between refreshes — the result
#              cache takes the correctness-mandated rebuild instead of
#              serving stale prefixes;
#   qstorm     a thread-pool storm of distinct queries through the
#              SearchGate admission path (queue_wait becomes visible);
#   longrange  a year-long query over two-tier downsampled data vs the
#              raw oracle (VM_DOWNSAMPLE_READ=0): >=20x fewer samples
#              (target 100x), >=10x lower p50, bit-exact result.
# ---------------------------------------------------------------------------

R13_SERIES = int(os.environ.get("VM_BENCH_R13_SERIES", "2048"))
R13_SAMPLES = int(os.environ.get("VM_BENCH_R13_SAMPLES", "360"))
R13_REFRESHES = int(os.environ.get("VM_BENCH_R13_REFRESHES", "16"))
LR_SERIES = int(os.environ.get("VM_BENCH_R13_LR_SERIES", "16"))
LR_DAYS = int(os.environ.get("VM_BENCH_R13_LR_DAYS", "365"))
DAY_MS = 86_400_000


def _r13_emit(scenario: str, payload: dict) -> None:
    path = f"BENCH_r13_{scenario}.json"
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps(payload))


def _r13_keys(n_series: int, gen) -> list:
    """One metric family, identity = (idx, g): bumping g for a slot is
    CHURN — a brand-new series through index insert + key-map miss."""
    if isinstance(gen, int):
        gen = [gen] * n_series
    return [(f'm{{idx="{i}",g="{gen[i]}",job="job-{i % 17}",'
             f'instance="host-{i % 64}"}}').encode()
            for i in range(n_series)]


def _r13_ingest(s, keys: list, ts2, vals2) -> None:
    from victoriametrics_tpu import native
    klens = np.fromiter((len(k) for k in keys), np.int64, len(keys))
    koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
    k = ts2.shape[1]
    s.add_rows_columnar(native.ColumnarRows(
        b"".join(keys), np.repeat(koffs, k), np.repeat(klens, k),
        ts2.reshape(-1).astype(np.int64),
        vals2.reshape(-1).astype(np.float64)))


def _r13_corpus(s, rng, t_start: int, keys: list):
    """R13_SERIES jittered counters x R13_SAMPLES @15s; returns the
    running counter values for the steady-state ingest to continue."""
    base = np.arange(R13_SAMPLES, dtype=np.int64) * 15_000 + t_start
    last_val = np.zeros(len(keys))
    chunk = 256
    for i0 in range(0, len(keys), chunk):
        i1 = min(i0 + chunk, len(keys))
        ts2 = np.sort(base[None, :] + rng.integers(
            -JITTER_MS, JITTER_MS + 1, (i1 - i0, R13_SAMPLES)), axis=1)
        vals2 = np.cumsum(rng.integers(0, 50, (i1 - i0, R13_SAMPLES)),
                          axis=1).astype(np.float64)
        last_val[i0:i1] = vals2[:, -1]
        _r13_ingest(s, keys[i0:i1], ts2, vals2)
    s.force_flush()
    s.force_merge()
    return last_val


def _r13_steady(api, s, kw, q, end0: int, duration: int, rng, keys,
                last_val, per_refresh=None, concurrent_flush=False):
    """The shared steady loop: live ingest + window advance per refresh
    through the cached-range executor, with the standard attribution
    snapshots. `per_refresh(i, end)` runs extra workload (churn,
    backfill) before the timed refresh; `concurrent_flush` overlaps a
    flush+merge with every timed refresh (the churn merge-pressure
    leg). Returns (lat, stats dict)."""
    import threading

    from victoriametrics_tpu.query.types import EvalConfig
    from victoriametrics_tpu.utils import flightrec

    def ingest_fresh(end_ms: int) -> None:
        incr = rng.integers(0, 50, (len(keys), 4))
        vals2 = last_val[:, None] + np.cumsum(incr, axis=1)
        last_val[:] = vals2[:, -1]
        ts2 = (end_ms - STEP +
               (np.arange(4, dtype=np.int64) + 1)[None, :] * 15_000 +
               rng.integers(-JITTER_MS, JITTER_MS + 1, (len(keys), 4)))
        ts2.sort(axis=1)
        _r13_ingest(s, keys, ts2, vals2)

    end = end0
    api._exec_range_cached(EvalConfig(start=end - duration, end=end,
                                      **kw), q, end)
    pre = []
    for _ in range(2):  # preflight: calibrate the slow-refresh trigger
        end += STEP
        ingest_fresh(end)
        t0 = time.perf_counter()
        api._exec_range_cached(EvalConfig(start=end - duration, end=end,
                                          **kw), q, end)
        pre.append(time.perf_counter() - t0)
    if "VM_SLOW_REFRESH_MS" not in os.environ:
        os.environ["VM_SLOW_REFRESH_MS"] = str(
            max(min(pre) * 1.25e3, 25.0))
    thresh_ms = float(os.environ["VM_SLOW_REFRESH_MS"])
    flight_id0 = flightrec.RECORDER.total()
    ph0, c0 = _phase_totals(), _cache_merge_totals()
    lat, leg_costs = [], []
    for i in range(R13_REFRESHES):
        end += STEP
        ingest_fresh(end)
        if per_refresh is not None:
            per_refresh(i, end)
        fl = None
        if concurrent_flush:
            fl = threading.Thread(
                target=lambda: (s.force_flush(), s.force_merge()))
            fl.start()
        ec = EvalConfig(start=end - duration, end=end, **kw)
        t0 = time.perf_counter()
        api._exec_range_cached(ec, q, end)
        lat.append(time.perf_counter() - t0)
        leg_costs.append(ec.cost)
        if fl is not None:
            fl.join()
    stats = {
        "phase": _phase_label(ph0, _phase_totals(), R13_REFRESHES),
        "cache": _cache_merge_delta(c0),
        "cost": _cost_leg_summary(leg_costs, lat),
        "flight": _leg_flight_summary(flight_id0, thresh_ms),
    }
    return lat, stats


def _r13_setup(tmp: str, downsample=None, retention_ms=None):
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.storage.storage import Storage
    kw = {}
    if downsample is not None:
        kw["downsample"] = downsample
    if retention_ms is not None:
        kw["retention_ms"] = retention_ms
    s = Storage(tmp, **kw)
    return s, PrometheusAPI(s, None)


def churn_main() -> None:
    """Scenario `churn`: identity turnover under merge pressure.

    Every refresh retires ~2% of the live fleet and births replacement
    identities (new g= label -> index inserts + key-map misses), and a
    flush+merge runs CONCURRENT with the timed refresh. Acceptance:
    vm_merge_gate_yields_total ticks (part writes defer to in-flight
    serving instead of stealing its cores) and refresh p99 stays within
    2x p50 — churn must degrade the MEDIAN honestly, not fabricate a
    tail cliff."""
    tmp = tempfile.mkdtemp(prefix="vmtpu-bench-churn-")
    rng = np.random.default_rng(13)
    try:
        s, api = _r13_setup(tmp)
        now_ms = int(time.time() * 1000)
        t_start = (now_ms - (R13_SAMPLES - 1) * 15_000) // STEP * STEP
        keys = _r13_keys(R13_SERIES, 0)
        gens = [0] * R13_SERIES
        last_val = _r13_corpus(s, rng, t_start, keys)
        q = "sum by (job)(rate(m[5m]))"
        duration = (R13_SAMPLES - 1) * 15_000 - 300_000
        end0 = t_start + -(-((R13_SAMPLES - 1) * 15_000 + JITTER_MS)
                           // STEP) * STEP
        kw = dict(step=STEP, storage=s, tpu=None)
        churn_n = max(1, R13_SERIES // 50)
        churned = 0

        def per_refresh(i, end):
            nonlocal churned
            lo = (i * churn_n) % R13_SERIES
            idxs = [(lo + j) % R13_SERIES for j in range(churn_n)]
            for j in idxs:
                gens[j] = i + 1            # new identity for the slot
                keys[j] = _r13_keys(R13_SERIES, gens)[j]
                last_val[j] = 0.0          # fresh counter from zero
            churned += churn_n

        lat, stats = _r13_steady(api, s, kw, q, end0, duration, rng,
                                 keys, last_val, per_refresh=per_refresh,
                                 concurrent_flush=True)
        p50 = float(np.median(lat)) * 1e3
        p99 = float(np.percentile(lat, 99)) * 1e3
        yields = stats["cache"]["merge_gate_yields"]
        assert yields > 0, \
            "churn loop never deferred a merge to serving"
        assert p99 <= 2 * p50, (p99, p50)
        _r13_emit("churn", {
            "scenario": "churn",
            "metric": f"series churn: {R13_SERIES} live series, "
                      f"{churn_n}/refresh replaced over "
                      f"{R13_REFRESHES} refreshes with concurrent "
                      f"flush+merge — merges deferred to serving "
                      f"{yields}x, p99/p50 {p99 / p50:.2f}",
            "value": round(p50, 2), "unit": "ms refresh p50",
            "series": R13_SERIES, "churned_total": churned,
            "refresh_p50_ms": round(p50, 2),
            "refresh_p99_ms": round(p99, 2),
            "refresh_ms": [round(x * 1e3, 2) for x in lat],
            "acceptance": {"merge_gate_yields_gt_0": yields > 0,
                           "p99_within_2x_p50": p99 <= 2 * p50},
            **stats,
        })
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def backfill_main() -> None:
    """Scenario `backfill`: historical chunks land between refreshes.

    Each refresh is preceded by an out-of-order ingest of a 15-minute
    historical chunk (2 days old) for every live series — the write
    path the remote-write backfill/migration tools exercise. The
    result cache must take the correctness-mandated rebuild (a cached
    prefix over a window that just changed underneath is a LIE), so
    the artifact records the rebuild/inplace split plus the sustained
    backfill rate alongside the refresh distribution."""
    tmp = tempfile.mkdtemp(prefix="vmtpu-bench-backfill-")
    rng = np.random.default_rng(17)
    try:
        s, api = _r13_setup(tmp)
        now_ms = int(time.time() * 1000)
        t_start = (now_ms - (R13_SAMPLES - 1) * 15_000) // STEP * STEP
        keys = _r13_keys(R13_SERIES, 0)
        last_val = _r13_corpus(s, rng, t_start, keys)
        q = "sum by (job)(rate(m[5m]))"
        duration = (R13_SAMPLES - 1) * 15_000 - 300_000
        end0 = t_start + -(-((R13_SAMPLES - 1) * 15_000 + JITTER_MS)
                           // STEP) * STEP
        kw = dict(step=STEP, storage=s, tpu=None)
        bf_base = t_start - 2 * DAY_MS
        bf_chunk = 60                      # 15min @ 15s per refresh
        bf_rows = [0]
        bf_secs = [0.0]

        def per_refresh(i, end):
            ts0 = bf_base + i * bf_chunk * 15_000
            ts2 = (ts0 + np.arange(bf_chunk, dtype=np.int64)[None, :]
                   * 15_000 + np.zeros((R13_SERIES, 1), np.int64))
            vals2 = np.cumsum(
                rng.integers(0, 50, (R13_SERIES, bf_chunk)),
                axis=1).astype(np.float64)
            t0 = time.perf_counter()
            _r13_ingest(s, keys, ts2, vals2)
            bf_secs[0] += time.perf_counter() - t0
            bf_rows[0] += R13_SERIES * bf_chunk

        lat, stats = _r13_steady(api, s, kw, q, end0, duration, rng,
                                 keys, last_val, per_refresh=per_refresh)
        p50 = float(np.median(lat)) * 1e3
        p99 = float(np.percentile(lat, 99)) * 1e3
        bf_rate = bf_rows[0] / max(bf_secs[0], 1e-9)
        _r13_emit("backfill", {
            "scenario": "backfill",
            "metric": f"backfill under serving: {bf_rows[0]} historical "
                      f"rows ({bf_rate / 1e6:.2f}M rows/s) interleaved "
                      f"with {R13_REFRESHES} refreshes — "
                      + (f"cache took {stats['cache']['rebuild']} "
                         f"rebuilds / {stats['cache']['inplace']} "
                         f"in-place merges"
                         if stats["cache"]["rebuild"]
                         or stats["cache"]["inplace"] else
                         "every refresh recomputed cold (the backfill "
                         "invalidates the cached window — correctness "
                         "over cache reuse)"),
            "value": round(p50, 2), "unit": "ms refresh p50",
            "series": R13_SERIES, "backfill_rows": bf_rows[0],
            "backfill_rows_per_s": int(bf_rate),
            "refresh_p50_ms": round(p50, 2),
            "refresh_p99_ms": round(p99, 2),
            "refresh_ms": [round(x * 1e3, 2) for x in lat],
            **stats,
        })
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def qstorm_main() -> None:
    """Scenario `qstorm`: a burst of DISTINCT queries through the
    SearchGate admission path — 8 client threads x 4 rounds x 16
    different (function, selector) combinations, caches off (every
    query is a first sight, the anti-dashboard). The per-phase split
    makes queue_wait visible; VM_SEARCH_CONCURRENCY is pinned to 4 so
    admission genuinely queues instead of vanishing on a wide host."""
    os.environ.setdefault("VM_SEARCH_CONCURRENCY", "4")
    import concurrent.futures as cf

    tmp = tempfile.mkdtemp(prefix="vmtpu-bench-qstorm-")
    rng = np.random.default_rng(23)
    try:
        from victoriametrics_tpu.query.exec import exec_query
        from victoriametrics_tpu.query.types import EvalConfig
        from victoriametrics_tpu.utils import flightrec
        s, _api = _r13_setup(tmp)
        now_ms = int(time.time() * 1000)
        t_start = (now_ms - (R13_SAMPLES - 1) * 15_000) // STEP * STEP
        keys = _r13_keys(R13_SERIES, 0)
        _r13_corpus(s, rng, t_start, keys)
        duration = (R13_SAMPLES - 1) * 15_000 - 300_000
        end = t_start + -(-((R13_SAMPLES - 1) * 15_000 + JITTER_MS)
                          // STEP) * STEP
        funcs = ["rate", "increase", "max_over_time", "avg_over_time"]
        queries = [f'sum by (instance)({fn}(m{{job="job-{j}"}}[5m]))'
                   for fn in funcs for j in (1, 3, 5, 7)]

        def one(q):
            ec = EvalConfig(start=end - duration, end=end, step=STEP,
                            storage=s, tpu=None, disable_cache=True)
            t0 = time.perf_counter()
            rows = exec_query(ec, q)
            dt = time.perf_counter() - t0
            assert rows, q
            return dt, ec.cost

        os.environ.setdefault("VM_SLOW_REFRESH_MS", "1000")
        flight_id0 = flightrec.RECORDER.total()
        ph0, c0 = _phase_totals(), _cache_merge_totals()
        lat, leg_costs = [], []
        rounds = 4
        t_wall = time.perf_counter()
        with cf.ThreadPoolExecutor(max_workers=8) as pool:
            for _ in range(rounds):
                for dt, cost in pool.map(one, queries):
                    lat.append(dt)
                    leg_costs.append(cost)
        wall = time.perf_counter() - t_wall
        n = len(lat)
        p50 = float(np.median(lat)) * 1e3
        p99 = float(np.percentile(lat, 99)) * 1e3
        d1 = _phase_totals()
        _r13_emit("qstorm", {
            "scenario": "qstorm",
            "metric": f"query storm: {n} distinct cold queries over "
                      f"{R13_SERIES} series via 8 threads at "
                      f"VM_SEARCH_CONCURRENCY="
                      f"{os.environ['VM_SEARCH_CONCURRENCY']} — "
                      f"{n / wall:.1f} qps, queue_wait "
                      f"{(d1['queue_wait'] - ph0['queue_wait']) * 1e3 / n:.0f}"
                      f"ms/query",
            "value": round(n / wall, 2), "unit": "queries/sec",
            "threads": 8, "distinct_queries": len(queries),
            "rounds": rounds,
            "query_p50_ms": round(p50, 2),
            "query_p99_ms": round(p99, 2),
            "queue_wait_ms_per_query": round(
                (d1["queue_wait"] - ph0["queue_wait"]) * 1e3 / n, 2),
            "phase": _phase_label(ph0, d1, n),
            "cache": _cache_merge_delta(c0),
            "cost": _cost_leg_summary(leg_costs, lat),
            "flight": _leg_flight_summary(
                flight_id0, float(os.environ["VM_SLOW_REFRESH_MS"])),
        })
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def longrange_main() -> None:
    """Scenario `longrange`: the downsampling headline (ISSUE 20).

    A year of 30s raw data under VM_DOWNSAMPLE=1d:5m,30d:1h, one
    re-rollup cycle, then the same year-long `sum_over_time(m[1d])`
    step-1d query through the tier-serving read path vs the raw oracle
    (VM_DOWNSAMPLE_READ=0). Acceptance: >=20x fewer samples read
    (target 100x), >=10x lower p50, bit-exact equality on the
    day-aligned grid."""
    tmp = tempfile.mkdtemp(prefix="vmtpu-bench-longrange-")
    rng = np.random.default_rng(29)
    try:
        from victoriametrics_tpu.query.exec import exec_query
        from victoriametrics_tpu.query.types import EvalConfig
        from victoriametrics_tpu.utils import flightrec
        s, _api = _r13_setup(tmp, downsample="1d:5m,30d:1h",
                             retention_ms=2 * 366 * DAY_MS)
        now_ms = int(time.time() * 1000)
        t_start = (now_ms // DAY_MS - LR_DAYS) * DAY_MS
        keys = _r13_keys(LR_SERIES, 0)
        n_per_day = DAY_MS // 30_000
        t0 = time.perf_counter()
        for d0 in range(0, LR_DAYS, 30):       # monthly ingest chunks
            nd = min(30, LR_DAYS - d0)
            base = (t_start + d0 * DAY_MS + np.arange(
                nd * n_per_day, dtype=np.int64) * 30_000)
            ts2 = np.broadcast_to(base, (LR_SERIES, base.size))
            vals2 = rng.integers(
                0, 1000, (LR_SERIES, base.size)).astype(np.float64)
            _r13_ingest(s, keys, np.ascontiguousarray(ts2), vals2)
            s.force_flush()
        ingest_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        s.run_downsample_cycle(now_ms=now_ms)
        ds_dt = time.perf_counter() - t0

        q = "sum_over_time(m[1d])"
        start = t_start + DAY_MS
        end = (now_ms // DAY_MS) * DAY_MS - DAY_MS
        raw_samples = LR_SERIES * LR_DAYS * n_per_day

        def leg(n_evals):
            s.reset_partial()
            lats, costs, rows = [], [], None
            id0 = flightrec.RECORDER.total()
            ph0 = _phase_totals()
            for _ in range(n_evals):
                ec = EvalConfig(start=start, end=end, step=DAY_MS,
                                storage=s, tpu=None, disable_cache=True)
                t0 = time.perf_counter()
                rows = exec_query(ec, q)
                lats.append(time.perf_counter() - t0)
                costs.append(ec.cost)
            return rows, {
                "p50_ms": round(float(np.median(lats)) * 1e3, 2),
                "samples_read": costs[-1].samples,
                "phase": _phase_label(ph0, _phase_totals(), n_evals),
                "cost": _cost_leg_summary(costs, lats),
                "flight": _leg_flight_summary(
                    id0, float(os.environ.get("VM_SLOW_REFRESH_MS",
                                              "1000"))),
            }

        os.environ.setdefault("VM_SLOW_REFRESH_MS", "10000")
        tier_rows, tier = leg(3)
        os.environ["VM_DOWNSAMPLE_READ"] = "0"
        try:
            raw_rows, raw = leg(3)
        finally:
            del os.environ["VM_DOWNSAMPLE_READ"]
        _assert_rows_equal(tier_rows, raw_rows)   # bit-exact, host path
        samples_ratio = raw["samples_read"] / max(tier["samples_read"], 1)
        p50_ratio = raw["p50_ms"] / max(tier["p50_ms"], 1e-9)
        assert samples_ratio >= 20, samples_ratio
        assert p50_ratio >= 10, p50_ratio
        _r13_emit("longrange", {
            "scenario": "longrange",
            "metric": f"long-range over tiers: {LR_DAYS}d x {LR_SERIES} "
                      f"series @30s ({raw_samples / 1e6:.1f}M raw "
                      f"samples), year query step 1d reads "
                      f"{samples_ratio:.0f}x fewer samples and runs "
                      f"{p50_ratio:.0f}x faster than the raw oracle, "
                      f"bit-exact",
            "value": round(samples_ratio, 1),
            "unit": "x fewer samples read",
            "tiers": "1d:5m,30d:1h",
            "raw_samples": raw_samples,
            "ingest_s": round(ingest_dt, 1),
            "downsample_pass_s": round(ds_dt, 1),
            "p50_speedup": round(p50_ratio, 1),
            "tier_leg": tier, "raw_leg": raw,
            "acceptance": {"samples_ratio_ge_20": samples_ratio >= 20,
                           "samples_ratio": round(samples_ratio, 1),
                           "p50_ratio_ge_10": p50_ratio >= 10,
                           "p50_ratio": round(p50_ratio, 1),
                           "oracle_bit_exact": True},
        })
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    import argparse
    _p = argparse.ArgumentParser(prog="bench.py")
    _p.add_argument("--scenario", default="dashboard",
                    choices=["dashboard", "fleet", "cluster", "churn",
                             "backfill", "qstorm", "longrange"],
                    help="dashboard: the classic rolling-window loop "
                         "(default, the BENCH_r* headline); fleet: N "
                         "subscribers x M shared-selector panels via "
                         "materialized streams (BENCH_r11); cluster: "
                         "elastic scale-out over real vmstorage "
                         "processes (CLUSTER_r12); churn/backfill/"
                         "qstorm/longrange: the r13 workload matrix "
                         "(BENCH_r13_<scenario>.json — identity "
                         "turnover under merge pressure, historical "
                         "ingest under serving, an admission-gated "
                         "query storm, and the downsample-tier "
                         "long-range headline)")
    _p.add_argument("--device", action="store_true",
                    help="with --scenario=fleet: the fleet-batched "
                         "DEVICE serving leg on the virtual 8-device "
                         "mesh (MULTICHIP_r07) — one fused launch per "
                         "interval for every resident stream")
    _args = _p.parse_args()
    if _args.scenario == "fleet" and _args.device:
        fleet_device_main()
    elif _args.scenario == "fleet":
        fleet_main()
    elif _args.scenario == "cluster":
        cluster_main()
    elif _args.scenario == "churn":
        churn_main()
    elif _args.scenario == "backfill":
        backfill_main()
    elif _args.scenario == "qstorm":
        qstorm_main()
    elif _args.scenario == "longrange":
        longrange_main()
    else:
        main()
