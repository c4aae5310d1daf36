"""What a run is made of, as functions of their sizes (so a CPU test can
rehearse them at a tiny size): the server under test, the data set, the
bulk load, and the check of sampled answers against the plain reference.

From the program this file takes only the system under test: vmsingle
built in this process as its main() builds it, driven over HTTP, plus
Storage.add_rows_columnar for the bulk load (set-up, not the timed path).
"""

from __future__ import annotations

import calendar
import http.client
import importlib.util
import json
import os
import socket
import sys
import time
import urllib.parse

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import compare  # noqa: E402
import reference  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quick_ack(sock) -> None:
    """Acknowledge what comes next at once (Linux's TCP_QUICKACK; armed
    anew for every answer, the kernel drops it again).  The program's
    server writes an answer's headers and its body in two writes and
    leaves Nagle's algorithm on, so on a KEPT connection a body under one
    segment (64 KB on loopback: histo8k.refresh's one row) waits for the
    client's delayed acknowledgement of the headers, 40 ms by the
    kernel's timer.  A connection a call never met it: a new connection
    acknowledges at once.  The stall is the pair of kernels' doing, not
    the query's, and a latency that holds 40 ms of it reads nothing of
    the program (PERF.md, Open questions: the server should set
    TCP_NODELAY, as the reference's does)."""
    if sock is not None and hasattr(socket, "TCP_QUICKACK"):
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_QUICKACK, 1)


class HTTPStatus(Exception):
    """The server answered with a status of 400 or above."""

    def __init__(self, status: int, body: bytes):
        super().__init__(f"HTTP {status}: {body[:200]!r}")
        self.status = status


class Client:
    """The client's side: one connection to the server, opened at the
    first call and kept, as a dashboard keeps its own.  `ticker` asks
    through the one `Server` is; a storm's helper makes one a client."""

    def __init__(self, host: str, port: int):
        self.srv_addr = (host, port)
        self.conn = None
        self.connects = 0

    def call(self, method: str, path: str, body=None) -> bytes:
        """One request over the one kept connection (HTTP/1.1,
        keep-alive), the body read whole; reopened once where the server
        had closed it."""
        for again in (True, False):
            if self.conn is None:
                host, port = self.srv_addr
                self.conn = http.client.HTTPConnection(host, port,
                                                       timeout=600)
                self.connects += 1
            try:
                self.conn.request(method, path, body=body)
                quick_ack(self.conn.sock)
                resp = self.conn.getresponse()
                data = resp.read()
                break
            except (http.client.RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError):
                self.hang_up()
                if not again:
                    raise
        if resp.will_close:     # a streamed answer's Connection: close
            self.hang_up()
        if resp.status >= 400:
            raise HTTPStatus(resp.status, data)
        return data

    def hang_up(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None

    def get(self, path: str, **params) -> bytes:
        if params:
            path += "?" + urllib.parse.urlencode(params)
        return self.call("GET", path)

    def post(self, path: str, body: bytes) -> None:
        self.call("POST", path, body)

    def metrics(self) -> dict:
        """/metrics as {series: value}."""
        out = {}
        for line in self.get("/metrics").decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                out[name] = float(val)
        return out

    def query_range(self, q: str, start_ms: int, end_ms: int, step_ms: int,
                    nocache: bool) -> bytes:
        """The raw answer; b"" where the server refused (HTTP error)."""
        params = dict(query=q, start=start_ms // 1000, end=end_ms // 1000,
                      step=step_ms // 1000)
        if nocache:
            params["nocache"] = "1"
        try:
            return self.get("/api/v1/query_range", **params)
        except HTTPStatus:
            return b""


class Server(Client):
    """vmsingle in this process: what apps/vmsingle.main() builds with
    -search.tpuBackend, served from a thread on a loopback port, and the
    harness's own client of it.  `flags` are the configuration's own
    (`server_flags` in its file: what the deployment it stands for starts
    vmsingle with), after the four every run passes."""

    def __init__(self, data_dir: str, flags=()):
        from victoriametrics_tpu.apps import vmsingle
        from victoriametrics_tpu.utils import logger
        args = vmsingle.parse_flags([
            f"-storageDataPath={data_dir}", "-httpListenAddr=127.0.0.1:0",
            "-search.tpuBackend", "-search.maxQueryDuration=300s",
            *flags])
        logger.set_level(args.loggerLevel)
        # build() attaches the device engine before it returns, or raises
        self.storage, self.srv, self.api = vmsingle.build(args)
        if self.api.tpu is None:
            raise RuntimeError("device engine not attached")
        self.srv.start()
        super().__init__("127.0.0.1", self.srv.port)

    def stop(self):
        self.hang_up()
        self.srv.stop()
        self.storage.close()


# A refresh mix moves simulated time one query step a tick, some hundred
# times faster than the wall clock, so the bulk ends far enough behind
# the wall clock that every tick a run can make stays in the past: a
# replay of two days ago, nothing future-dated.  The reckoning, in query
# steps: a window is run_seconds = 50 s (BENCHMARK.json; 51 is the most
# the contract allows) and the fastest tick allowed for is 20 ms (the
# fastest cell's tick is 66-74 ms with the client's text off its clock,
# PR 35: a quarter of it), so WINDOW_TICKS = 50 s / 20 ms = 2500; the
# warm-up's pre-roll (110 steps) and its WARM_TICKS (3) go before the
# window, with room for a longer pre-roll: SETUP_STEPS = 370.  At a 60 s
# step, with WALL_MARGIN_MS: (2500 + 370) x 60 s + 10 min = 48 h.
WINDOW_TICKS = 2500
SETUP_STEPS = 370
# ... and a window that would come within this of the wall clock fails
# the run, loudly, before it measures a server answering for the future
WALL_MARGIN_MS = 10 * 60_000


def month_start_ms(ms: int) -> int:
    """The first millisecond of the UTC month that holds `ms`."""
    t = time.gmtime(ms // 1000)
    return calendar.timegm((t.tm_year, t.tm_mon, 1, 0, 0, 0)) * 1000


def anchor(now_ms: int, step: int, reach_ms: int,
           ingests: bool = True) -> tuple:
    """-> (newest, latest): where the bulk's newest sample is aimed and
    the ceiling no window may pass.  `newest` lies (WINDOW_TICKS +
    SETUP_STEPS) steps under `latest`, and `latest` WALL_MARGIN_MS behind
    the wall clock; `reach_ms` is how far under `newest` the bulk's first
    sample can lie.  A configuration none of whose mixes ingests
    (`"ingests": false` in its file) needs no step free above its bulk:
    its `newest` IS `latest` (at an hour's step the ticks' room would be
    119 days).  Everything a run can touch lies in ONE calendar
    month (UTC), the storage's partition: where that span would hold a
    month's end it moves back as a whole, `latest` to the month's end less
    a step, so no run loads two partitions for one and no tick opens a
    new one inside a window."""
    room = (WINDOW_TICKS + SETUP_STEPS) * step if ingests else 0
    latest = now_ms - WALL_MARGIN_MS
    first_of_month = month_start_ms(latest)
    if latest - room - reach_ms < first_of_month:
        latest = first_of_month - step
        if latest - room - reach_ms < month_start_ms(latest):
            raise ValueError("the run's span does not fit a calendar month")
    return latest - room, latest


def exposition(keys: list, ts2: np.ndarray, vals2: np.ndarray) -> bytes:
    """Prometheus text exposition with timestamps, one line a sample."""
    rows = []
    for key, vs, tss in zip(keys, vals2.astype(np.int64).tolist(),
                            ts2.tolist()):
        k = key.decode()
        rows.extend(f"{k} {v} {t}" for v, t in zip(vs, tss))
    return ("\n".join(rows) + "\n").encode()


class Dataset:
    """The deployment's samples, anchored behind the wall clock by
    `anchor` (a literal timestamp would sooner or later fall out of
    retention), and the grid the queries walk.  Keeps every sample it
    handed out: the reference reads them, never the program's storage.
    Where the configuration states a dedup interval (`dedup_interval_s`:
    the server is started with -dedup.minScrapeInterval), a query sees
    the survivors of what was handed out (`visible`), and only there."""

    def __init__(self, cfg: dict, seed: int, now_ms: int):
        self.cfg = cfg
        self.step = int(cfg["query_step_s"] * 1000)
        self.window = int(cfg["window_s"] * 1000)
        self.scrape = int(cfg["scrape_interval_s"] * 1000)
        n_samples = int(cfg["range_h"] * 3_600_000) // self.scrape
        self.dedup = int(cfg.get("dedup_interval_s", 0) * 1000)
        jitter = int(cfg["jitter_s"] * 1000)
        if jitter >= self.step:
            raise ValueError("jitter_s has to lie under query_step_s")
        span = (n_samples - 1) * self.scrape
        # the first sample lies up to a step (the grid's rounding) and the
        # jitter under newest - span; the newest tail's up to the jitter
        # over `latest`, which `anchor` keeps a step inside the month
        now_ms, self.latest = anchor(now_ms, self.step,
                                     span + self.step + jitter,
                                     cfg.get("ingests", True))
        self.rng = np.random.default_rng(seed)
        self.gen = load_module("deployments", cfg["deployment"]).Deployment(cfg)
        self.labels = self.gen.labels()
        self.keys = [(l["__name__"] + "{" + ",".join(
            f'{k}="{v}"' for k, v in sorted(l.items()) if k != "__name__")
            + "}").encode() for l in self.labels]
        self.t_start = (now_ms - span) // self.step * self.step
        # the first window ends BEYOND every bulk sample, jitter included,
        # so fresh tails never interleave with the bulk
        self.end = self.t_start + -(-(span + jitter) // self.step) * self.step
        self.duration = span // self.step * self.step - self.window
        self.ts, self.vals = self.gen.scrapes(
            self.rng, self.t_start - self.scrape, n_samples)
        self.tails = []
        self.work_memo = {}     # query_work's
        self._flat = None       # samples_between's

    @property
    def start(self) -> int:
        return self.end - self.duration

    def room(self) -> int:
        """The ticks of one query step left under the ceiling (none
        where the configuration never ingests)."""
        return max(0, (self.latest - self.end) // self.step)

    def _move(self, span: int) -> None:
        if self.end + span > self.latest:
            raise RuntimeError(
                "the window's simulated time has caught up with the "
                "ceiling: raise harness.WINDOW_TICKS")
        self.end += span

    def advance(self, steps: int = 1):
        """Move the window `steps` query steps on and make their scrapes."""
        if self.rng is None:
            raise RuntimeError("the stream of tails was handed to a "
                               "producer: take its ticks")
        span = steps * self.step
        self._move(span)
        tail = self.gen.scrapes(self.rng, self.end - span,
                                span // self.scrape)
        self.tails.append(tail)
        return tail

    def hand_over(self) -> dict:
        """Everything the stream of tails depends on from here on, for
        the one producer that goes on drawing it (traffic/producer.py);
        this object then draws no more and takes the producer's ticks."""
        state = dict(cfg=self.cfg, keys=self.keys, end=self.end,
                     step=self.step, scrape=self.scrape,
                     rng=self.rng.bit_generator.state,
                     gen={k: v for k, v in vars(self.gen).items()
                          if k != "cfg"})
        self.rng = None
        return state

    def take(self, tail) -> None:
        """Move the window one query step on, onto the scrapes `tail`
        that the producer made for it."""
        self._move(self.step)
        self.tails.append(tail)

    def samples_between(self, idx: np.ndarray, lo: int, hi: int,
                        n_tails: int) -> int:
        """How many samples of the rows `idx`, of the bulk and the first
        n_tails tails, lie in (lo, hi].  The bulk's rows are sorted, so
        its share is two binary searches a row in ONE flat array made
        once (row r's times shifted by r << 42, as reference._counts_le
        shifts them): a refresh cell's every tick is another range, and a
        pass over 47 M timestamps a tick cost a traced run of 190 ticks
        four minutes.  Under a dedup interval the SURVIVORS are counted:
        what the query needs read, not what the replicas wrote.  The flat
        array then holds the bulk's survivors but each row's newest,
        whose window the first tail may go on filling: that one is
        counted with the tails, of which each window's newest counts (no
        sample is older than one handed out before it)."""
        if self._flat is None:
            rows = np.arange(self.ts.shape[0], dtype=np.int64)[:, None]
            flat = self.ts + (rows << 42)
            if self.dedup:
                closed = reference.newest_of_window(self.ts, self.dedup)
                closed[:, -1] = False
                flat = flat[closed]
            self._flat = flat.ravel()
        off = idx.astype(np.int64) << 42
        n = int((np.searchsorted(self._flat, hi + off, side="right") -
                 np.searchsorted(self._flat, lo + off, side="right")).sum())
        tails = [ts[idx] for ts, _ in self.tails[:n_tails]]
        if self.dedup:
            sub = np.concatenate([self.ts[idx, -1:]] + tails, axis=1)
            keep = reference.newest_of_window(sub, self.dedup)
            return n + int((keep & (sub > lo) & (sub <= hi)).sum())
        return n + sum(int(((sub > lo) & (sub <= hi)).sum()) for sub in tails)

    def snapshot(self, n_tails: int):
        """Every sample handed out up to the n_tails-th tail."""
        tails = self.tails[:n_tails]
        if not tails:       # the bulk alone: no copy of 47 M samples
            return self.ts, self.vals
        return (np.concatenate([self.ts] + [t for t, _ in tails], axis=1),
                np.concatenate([self.vals] + [v for _, v in tails], axis=1))

    def visible(self, n_tails: int):
        """What a query may see of `snapshot(n_tails)`: all of it, or
        under a dedup interval each row's survivors (reference.dedup)."""
        ts, vals = self.snapshot(n_tails)
        if self.dedup:
            ts, vals = reference.dedup_rows(ts, vals, self.dedup)
        return ts, vals


def load_columnar(server: Server, data: Dataset, ts: np.ndarray,
                  vals: np.ndarray, chunk: int = 256) -> int:
    """Bulk load of [S, k] samples (set-up only: the bulk, the warm-up's
    pre-roll) through Storage.add_rows_columnar, the pipeline HTTP ingest
    itself ends in; returns the samples loaded."""
    from victoriametrics_tpu import native
    keybuf = b"".join(data.keys)
    klens = np.fromiter((len(k) for k in data.keys), np.int64, len(data.keys))
    koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
    n = ts.shape[1]
    for i0 in range(0, len(data.keys), chunk):
        i1 = min(i0 + chunk, len(data.keys))
        server.storage.add_rows_columnar(native.ColumnarRows(
            keybuf, np.repeat(koffs[i0:i1], n), np.repeat(klens[i0:i1], n),
            np.ascontiguousarray(ts[i0:i1]).reshape(-1),
            np.ascontiguousarray(vals[i0:i1]).reshape(-1)))
    return ts.size


def check_answers(data: Dataset, records: list, round_rollup=None) -> dict:
    """The numbers compared, over the sampled answers: each against the
    reference on the samples that had been acknowledged when it was asked.
    With `round_rollup` (the control) the reference computed in that lower
    precision stands in the program's place and the bodies are not read."""
    results = []
    unreadable = 0
    for r in records:
        ts, vals = data.visible(r["n_tails"])
        # a query's own step where its record holds one (traffic/
        # intervals.py), else the configuration's
        step = r.get("step", data.step)
        grid = np.arange(r["start"], r["end"] + 1, step, dtype=np.int64)
        ast = reference.parse(r["query"])
        kind, labels, ref = reference.evaluate(ast, data.labels, ts, vals, grid)
        if round_rollup is not None:
            got = control_answer(*reference.evaluate(
                ast, data.labels, ts, vals, grid, round_rollup))
        else:
            ok, got = compare.parse_answer(r["body"], r["start"], r["end"],
                                           step)
            unreadable += not ok
        results.append(compare.compare(kind, got, labels, ref))
    out = compare.worst(results)
    out["unreadable"] = unreadable
    out["answers"] = len(records)
    return out


def control_answer(kind: str, labels: list, values: np.ndarray) -> dict:
    """What a server would have answered had it computed `values`: all-NaN
    rows dropped, a topk's choice made."""
    if kind.startswith("topk:"):
        k = int(kind[5:])
        filled = np.where(np.isnan(values), -np.inf, values)
        order = np.argsort(-filled, axis=0, kind="stable")[:k]
        keep = np.zeros(values.shape, dtype=bool)
        keep[order, np.arange(values.shape[1])[None, :]] = True
        values = np.where(keep & ~np.isnan(values), values, np.nan)
    return {compare.labels_key(l): values[i] for i, l in enumerate(labels)
            if not np.isnan(values[i]).all()}


def query_work(data: Dataset, asked: dict) -> dict:
    """What one asked query needs moved, for the roofline and the scan
    rate: the real samples of the matched series in the fetched range
    (start - window, end], the window being the query's own rollup's, and
    the values of its answer (a row for every group the query leaves, of
    a topk its k).  Kept on the data set by the query's text and range:
    a window asks some of them a hundred times."""
    memo = data.work_memo
    step = asked.get("step", data.step)
    key = (asked["query"], asked["start"], asked["end"], asked["n_tails"],
           step)
    if key not in memo:
        ast = reference.parse(asked["query"])
        idx = reference.select(data.labels, *reference.selector(ast))
        lo, hi = asked["start"] - reference.window_of(ast), asked["end"]
        samples = data.samples_between(idx, lo, hi, asked["n_tails"])
        rows = len(reference.row_labels(ast, data.labels))
        if ast[0] == "topk":
            rows = min(ast[1], rows)
        steps = (asked["end"] - asked["start"]) // step + 1
        memo[key] = {"samples": samples, "out_values": rows * steps}
    return memo[key]
