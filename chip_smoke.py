#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the served device path still
starts on the chip.  One process, one chip.

Starts vmsingle IN THIS PROCESS the way its main() does (parse_flags ->
build -> serve thread) with -search.tpuBackend, loads the repo's own
dashboard deployment at full size over HTTP (8192 counter series x 1440
samples, 6 h at 15 s with +-2 s jitter, 256 instances, data from --seed,
anchored on the wall clock), asks the queries that cover the device routes
over HTTP query_range, compares every answer with the plain reference (the
same query evaluated in-process on the same storage with tpu=None: host
f64), and proves from the program's own /metrics counters that the device
did the work.

    python3 chip_smoke.py               # needs one TPU chip
    python3 chip_smoke.py --four-chips  # the cross-chip path only (4 chips)

It never sets JAX_PLATFORMS, never falls back and catches no phase's
failure: any exception or failed comparison is a non-zero exit, and so is
a machine where JAX finds no TPU.  The last line of stdout is one JSON
object {"ok": true, "device": {...}}; it is printed only when every phase
passed.  The wall times it prints are a smoke's observations, not a
benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import urllib.parse
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# the dashboard deployment (BASELINE.md config 2 shape)
N_SERIES = 8192
N_SAMPLES = 1440          # 6 h at 15 s
N_INSTANCES = 256
SCRAPE_MS = 15_000
JITTER_MS = 2_000
STEP_MS = 60_000
WINDOW_MS = 300_000
TAIL_SCRAPES = 4          # one step of fresh scrapes per refresh
METRIC = "http_requests_total"

Q_FUSED = f"sum by (instance)(rate({METRIC}[5m]))"
# matchers on job keep n_series/17 series (482 at full size, >= 64)
Q_PER_SERIES = f'rate({METRIC}{{job="job-3"}}[5m])'
Q_ADDBACK = f'max_over_time({METRIC}{{job="job-5"}}[5m])'
Q_TOPK = f"topk(10, rate({METRIC}[5m]))"

# device f32 rebased tiles vs host f64: the bounds tests/test_f32_tiles.py
# states (relative to max(|host|, 1e-3))
RTOL_DIRECT = 1e-5        # shift-invariant funcs, fused or per series
RTOL_ADDBACK = 1e-6       # F32_AFFINE funcs with the host f64 addback


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg) -> None:
    """A failed check is a non-zero exit (not `assert`: python -O would
    remove every one and pass vacuously)."""
    if not ok:
        raise AssertionError(msg)


class Phase:
    """Wall time of one phase, printed as a smoke's observation."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            log(f"phase {self.name}: {time.perf_counter() - self.t0:.2f} s "
                "wall (observed by the smoke, not a benchmark)")


# -- phases: each takes its sizes as arguments, so a CPU test or a scratch
# -- shell can rehearse the control flow at a tiny size ---------------------

def build_native():
    """Rebuild the native library from the committed sources on THIS
    machine (it is built -march=native and git-ignored) and refuse the
    NumPy fallback: vm_assemble_part is part of the served fetch path."""
    subprocess.run(["make", "-B", "-C",
                    os.path.join(ROOT, "victoriametrics_tpu", "native")],
                   check=True)
    from victoriametrics_tpu import native
    check(native.available(),
          "native library unavailable after make: the served fetch path "
          "would run its NumPy fallback, which is a different program")


class Server:
    """vmsingle in this process: what apps/vmsingle.main() builds, served
    from a thread on a loopback port."""

    def __init__(self, data_dir: str):
        from victoriametrics_tpu.apps import vmsingle
        from victoriametrics_tpu.utils import logger
        args = vmsingle.parse_flags([
            f"-storageDataPath={data_dir}", "-httpListenAddr=127.0.0.1:0",
            "-search.tpuBackend", "-search.maxQueryDuration=300s"])
        logger.set_level(args.loggerLevel)
        # build() attaches the device engine before it returns, or raises
        self.storage, self.srv, self.api = vmsingle.build(args)
        check(self.api.tpu is not None, "device engine not attached")
        self.srv.start()
        self.url = f"http://127.0.0.1:{self.srv.port}"

    def stop(self):
        self.srv.stop()
        self.storage.close()

    def get(self, path: str, **params) -> bytes:
        url = self.url + path
        if params:
            url += "?" + urllib.parse.urlencode(params)
        with urllib.request.urlopen(url, timeout=600) as r:
            return r.read()

    def post(self, path: str, body: bytes) -> None:
        req = urllib.request.Request(self.url + path, data=body,
                                     method="POST")
        with urllib.request.urlopen(req, timeout=600) as r:
            r.read()

    def metrics(self) -> dict:
        """/metrics as {series: value}."""
        out = {}
        for line in self.get("/metrics").decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                out[name] = float(val)
        return out

    def query_range(self, q: str, start_ms: int, end_ms: int,
                    nocache: bool = False) -> dict:
        """HTTP query_range -> {labels: [T] float64, NaN where absent}."""
        params = dict(query=q, start=start_ms // 1000, end=end_ms // 1000,
                      step=STEP_MS // 1000)
        if nocache:
            params["nocache"] = "1"
        body = json.loads(self.get("/api/v1/query_range", **params))
        check(body["status"] == "success", body)
        check(not body["isPartial"], "partial result")
        n = (end_ms - start_ms) // STEP_MS + 1
        out = {}
        for r in body["data"]["result"]:
            row = np.full(n, np.nan)
            for t, v in r["values"]:
                row[round((t * 1000 - start_ms) / STEP_MS)] = float(v)
            out[_labels_key(r["metric"])] = row
        return out

    def reference(self, q: str, start_ms: int, end_ms: int) -> dict:
        """The plain reference: the same query on the same storage with
        no device engine and no cache (host f64 path)."""
        from victoriametrics_tpu.query.exec import exec_query
        ec = self.api._ec(start_ms, end_ms, STEP_MS)
        ec.tpu = None
        ec.disable_cache = True
        return {_labels_key(r.metric_name.to_dict()): np.asarray(r.values)
                for r in exec_query(ec, q)
                if not np.isnan(r.values).all()}


def _labels_key(d: dict) -> tuple:
    return tuple(sorted(d.items()))


class Dashboard:
    """The generated deployment: series keys, the running counter values,
    and the grid the queries walk."""

    def __init__(self, n_series, n_samples, n_instances, seed, now_ms):
        self.n_series, self.n_samples = n_series, n_samples
        self.rng = np.random.default_rng(seed)
        self.keys = [f'{METRIC}{{idx="{i}",instance="host-{i % n_instances}"'
                     f',job="job-{i % 17}"}}' for i in range(n_series)]
        # anchored on the wall clock (a literal timestamp would sooner or
        # later fall out of retention and compare empty with empty)
        span = (n_samples - 1) * SCRAPE_MS
        self.t_start = (now_ms - span) // STEP_MS * STEP_MS
        # the first window ends BEYOND every initial sample, jitter
        # included, so fresh tails never interleave with the bulk
        self.end = self.t_start + -(-(span + JITTER_MS) // STEP_MS) * STEP_MS
        self.duration = span // STEP_MS * STEP_MS - WINDOW_MS
        self.last_val = np.zeros(n_series, dtype=np.int64)

    def _text(self, i0, ts2, vals2) -> bytes:
        rows = []
        for j in range(ts2.shape[0]):
            k = self.keys[i0 + j]
            rows.append("\n".join(
                [f"{k} {v} {t}" for v, t in zip(vals2[j].tolist(),
                                               ts2[j].tolist())]))
        return ("\n".join(rows) + "\n").encode()

    def load(self, server: Server, chunk: int = 256) -> int:
        """Bulk load over HTTP /api/v1/import/prometheus in chunks of
        `chunk` series; returns the bytes posted."""
        base = np.arange(self.n_samples, dtype=np.int64) * SCRAPE_MS + \
            self.t_start
        posted = 0
        for i0 in range(0, self.n_series, chunk):
            n = min(chunk, self.n_series - i0)
            ts2 = np.sort(base[None, :] + self.rng.integers(
                -JITTER_MS, JITTER_MS + 1, (n, self.n_samples)), axis=1)
            vals2 = np.cumsum(self.rng.integers(
                0, 50, (n, self.n_samples)), axis=1)
            self.last_val[i0:i0 + n] = vals2[:, -1]
            body = self._text(i0, ts2, vals2)
            server.post("/api/v1/import/prometheus", body)
            posted += len(body)
        return posted

    def ingest_tail(self, server: Server) -> None:
        """Advance the window one step and post that step's fresh
        scrapes (TAIL_SCRAPES per series in (end - step, end])."""
        self.end += STEP_MS
        incr = self.rng.integers(0, 50, (self.n_series, TAIL_SCRAPES))
        vals2 = self.last_val[:, None] + np.cumsum(incr, axis=1)
        self.last_val = vals2[:, -1]
        ts2 = (self.end - STEP_MS +
               (np.arange(TAIL_SCRAPES, dtype=np.int64) + 1)[None, :] *
               SCRAPE_MS + self.rng.integers(
                   -JITTER_MS, JITTER_MS + 1, (self.n_series, TAIL_SCRAPES)))
        ts2.sort(axis=1)
        server.post("/api/v1/import/prometheus", self._text(0, ts2, vals2))

    @property
    def start(self) -> int:
        return self.end - self.duration


def compare(got: dict, want: dict, rtol: float, label: str,
            min_series: int) -> None:
    """Device answer vs host reference, the tests/test_f32_tiles.py way:
    same series, same NaN gaps, |dev - host| / max(|host|, 1e-3) < rtol.
    Non-empty first: empty == empty proves nothing."""
    check(len(want) >= min_series,
          f"{label}: reference has {len(want)} series, expected >= "
          f"{min_series}")
    check(set(got) == set(want),
          f"{label}: series differ ({len(got)} served, {len(want)} reference)")
    worst = 0.0
    n_vals = 0
    for k, host in want.items():
        dev = got[k]
        check(np.array_equal(np.isnan(dev), np.isnan(host)),
              f"{label}: NaN gaps differ for {dict(k)}")
        m = ~np.isnan(host)
        n_vals += int(m.sum())
        if m.any():
            err = np.abs(dev[m] - host[m]) / np.maximum(np.abs(host[m]), 1e-3)
            worst = max(worst, float(err.max()))
    check(n_vals > 0, f"{label}: no values")
    check(worst < rtol, f"{label}: max rel err {worst:.3g} >= {rtol}")
    log(f"query {label}: {len(got)} series, {n_vals} values, "
        f"max rel err vs host f64 {worst:.3g} (< {rtol})")


def kernel_counts(m: dict, phase: str) -> dict:
    """{kernel: count} of vm_tpu_kernel_duration_seconds for one phase."""
    pre = "vm_tpu_kernel_duration_seconds_count{"
    out = {}
    for name, v in m.items():
        if name.startswith(pre) and f'phase="{phase}"' in name:
            out[name.split('kernel="')[1].split('"')[0]] = int(v)
    return out


def delta(after: dict, before: dict, name: str) -> float:
    return after.get(name, 0.0) - before.get(name, 0.0)


UP = "vm_device_bytes_uploaded_total"
DOWN = "vm_device_bytes_downloaded_total"
HITS = "vm_device_window_cache_hits_total"
COMPILES = "vm_device_backend_compiles_total"
CACHE_HITS = "vm_device_fleet_compile_cache_hits_total"


def ask_twice(server, dash, q, rtol, label, min_series):
    """One device route over HTTP: the served (cached) path, then
    nocache=1 — the same kernel shapes again, so the second call is a
    pure execute — both against the host reference."""
    want = server.reference(q, dash.start, dash.end)
    compare(server.query_range(q, dash.start, dash.end), want, rtol,
            label, min_series)
    compare(server.query_range(q, dash.start, dash.end, nocache=True),
            want, rtol, label + " nocache=1", min_series)


def refreshes(server, dash, fused_kernel, n_groups):
    """Two rolling refreshes of the fused query: ingest a fresh tail,
    advance the window one step, ask again.  The resident window serves
    them (donated append_tile + tail kernel), uploading only the tail;
    the second one compiles nothing."""
    ups = []
    for r in (1, 2):
        dash.ingest_tail(server)
        m0 = server.metrics()
        got = server.query_range(Q_FUSED, dash.start, dash.end)
        m1 = server.metrics()
        compare(got, server.reference(Q_FUSED, dash.start, dash.end),
                RTOL_DIRECT, f"fused refresh {r}", n_groups)
        hits = delta(m1, m0, HITS)
        up = delta(m1, m0, UP)
        compiles = delta(m1, m0, COMPILES)
        ex = kernel_counts(m1, "execute").get(fused_kernel, 0) - \
            kernel_counts(m0, "execute").get(fused_kernel, 0)
        log(f"refresh {r}: window_cache_hits +{hits:.0f}, uploaded "
            f"{up:.0f} B, downloaded {delta(m1, m0, DOWN):.0f} B, "
            f"backend_compiles +{compiles:.0f}, {fused_kernel} "
            f"execute +{ex}")
        check(hits >= 1, f"refresh {r} missed the resident window")
        ups.append(up)
        if r == 2:
            check(compiles == 0,
                  f"second refresh compiled {compiles:.0f} programs")
            check(ex >= 1, f"{fused_kernel} did not execute on refresh 2")
    return ups


def resident_tile(server):
    """The fused query's RollingTile, from the engine's window cache."""
    from victoriametrics_tpu.query.tpu_engine import RollingTile
    wc = server.api.tpu.window_cache()
    tiles = [v for v in wc._entries.values() if isinstance(v, RollingTile)]
    check(len(tiles) == 1, f"{len(tiles)} resident windows, expected 1")
    return tiles[0]


def run(n_series, n_samples, n_instances, seed, four_chips: bool) -> None:
    """Every phase after the native rebuild, in order; raises on the first
    failure.  `four_chips` runs only the cross-chip path: the fused query
    and its refreshes on the series mesh over every visible device."""
    import jax
    data_dir = tempfile.mkdtemp(prefix="chip-smoke-")
    server = None
    try:
        with Phase("server start (device init + warmup compiles)"):
            server = Server(data_dir)
        engine = server.api.tpu
        n_shards = engine.series_shards()
        check(n_shards == (len(jax.devices()) if four_chips else 1),
              f"engine mesh has {n_shards} series shards")
        fused_kernel = "sharded_rollup_aggregate" if four_chips \
            else "rollup_aggregate_tile"
        log(f"engine: {np.dtype(engine.value_dtype).name} tiles, "
            f"{n_shards} series shard(s), x64="
            f"{jax.config.jax_enable_x64}")

        dash = Dashboard(n_series, n_samples, n_instances, seed,
                         int(time.time() * 1000))
        with Phase("load"):
            posted = dash.load(server)
            server.get("/internal/force_flush")
        log(f"load: {n_series} series x {n_samples} samples = "
            f"{n_series * n_samples} samples, all over HTTP "
            f"/api/v1/import/prometheus ({posted} B of text), then "
            "/internal/force_flush")
        n_job = len(range(3, n_series, 17))

        m_start = server.metrics()
        with Phase("fused query, cold"):
            want = server.reference(Q_FUSED, dash.start, dash.end)
            got = server.query_range(Q_FUSED, dash.start, dash.end)
        compare(got, want, RTOL_DIRECT, "fused sum(rate) cold", n_instances)
        m_cold = server.metrics()
        cold_up = delta(m_cold, m_start, UP)
        log(f"cold: uploaded {cold_up:.0f} B, downloaded "
            f"{delta(m_cold, m_start, DOWN):.0f} B, backend_compiles "
            f"+{delta(m_cold, m_start, COMPILES):.0f}")
        check(cold_up > 0 and delta(m_cold, m_start, DOWN) > 0,
              "the cold query moved no bytes over the link")

        if not four_chips:
            with Phase("per-series, addback and topk queries"):
                ask_twice(server, dash, Q_PER_SERIES, RTOL_DIRECT,
                          "per-series rate", min(n_job, 64))
                ask_twice(server, dash, Q_ADDBACK, RTOL_ADDBACK,
                          "max_over_time (host addback)", min(n_job, 64))
                ask_twice(server, dash, Q_TOPK, RTOL_DIRECT,
                          "topk(10, rate)", 10)

        with Phase("two rolling refreshes"):
            ups = refreshes(server, dash, fused_kernel, n_instances)
        # a refresh ships its tail columns, not the window: 8 padded
        # columns x (int32 ts + host f64 value) + a count per series
        log(f"refresh upload / cold upload: {max(ups) / cold_up:.4f}")
        check(max(ups) <= 128 * n_series and max(ups) * 4 < cold_up,
              f"a refresh uploaded {max(ups):.0f} B, cold {cold_up:.0f} B")
        rt = resident_tile(server)
        log(f"resident window: tile {tuple(rt.tiles[0].shape)} "
            f"{rt.tiles[1].dtype}, {rt.appends} donated appends")
        check(rt.appends == 2, "append_tile did not run on each refresh")

        m_end = server.metrics()
        comp, execd = kernel_counts(m_end, "compile"), \
            kernel_counts(m_end, "execute")
        log(f"kernels compiled+ran: {comp}")
        log(f"kernels executed (no compile): {execd}")
        want_exec = [fused_kernel] if four_chips else \
            [fused_kernel, "rollup_tile", "topk_select_tile"]
        exec0 = kernel_counts(m_start, "execute")
        for k in want_exec:
            check(execd.get(k, 0) > exec0.get(k, 0),
                  f"kernel {k} never executed on the device")
        log(f"totals: uploaded {m_end[UP]:.0f} B, downloaded "
            f"{m_end[DOWN]:.0f} B, window_cache_hits {m_end[HITS]:.0f}, "
            f"backend_compiles {m_end[COMPILES]:.0f}, "
            f"compile_cache_hits {m_end.get(CACHE_HITS, 0):.0f}")
        check(m_end[COMPILES] + m_end.get(CACHE_HITS, 0) > 0,
              "compile telemetry never ticked: the counters prove nothing")

        if four_chips:
            # code that has only met one chip may put everything on the
            # first: the resident tile must span every chip, and every
            # chip must hold bytes
            spans = len(rt.tiles[1].sharding.device_set)
            check(spans == n_shards,
                  f"resident tile spans {spans} device(s), not {n_shards}")
        for d in jax.devices():
            st = d.memory_stats()  # None on XLA-CPU (rehearsals)
            log(f"device {d.id}: memory_stats " + (
                "not reported" if st is None else
                f"bytes_in_use {st['bytes_in_use']}, peak_bytes_in_use "
                f"{st['peak_bytes_in_use']}"))
            if four_chips and (st is not None or d.platform == "tpu"):
                check(st["bytes_in_use"] > 0,
                      f"device {d.id} holds nothing: the tile is not sharded")
    finally:
        if server is not None:
            server.stop()
        shutil.rmtree(data_dir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip path (needs 4 chips)")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devs) != want:
        print(f"chip_smoke: {len(devs)} chips visible, this mode needs "
              f"{want}" + ("" if args.four_chips else
                           " (four chips: --four-chips)"), file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device['platform']} / {device['kind']} / "
        f"{device['count']} chip(s)")
    with Phase("total"):
        with Phase("native rebuild"):
            build_native()
        run(N_SERIES, N_SAMPLES, N_INSTANCES, args.seed, args.four_chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
