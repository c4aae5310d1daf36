"""What a run is made of, as functions of their sizes (so a CPU test can
rehearse them at a tiny size): the server under test, the data set, the
bulk load, and the check of sampled answers against the plain reference.

From the program this file takes only the system under test: vmsingle
built in this process as its main() builds it, driven over HTTP, plus
Storage.add_rows_columnar for the bulk load (set-up, not the timed path).
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import urllib.error
import urllib.parse
import urllib.request

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


class Server:
    """vmsingle in this process: what apps/vmsingle.main() builds with
    -search.tpuBackend, served from a thread on a loopback port."""

    def __init__(self, data_dir: str):
        from victoriametrics_tpu.apps import vmsingle
        from victoriametrics_tpu.utils import logger
        args = vmsingle.parse_flags([
            f"-storageDataPath={data_dir}", "-httpListenAddr=127.0.0.1:0",
            "-search.tpuBackend", "-search.maxQueryDuration=300s"])
        logger.set_level(args.loggerLevel)
        # build() attaches the device engine before it returns, or raises
        self.storage, self.srv, self.api = vmsingle.build(args)
        if self.api.tpu is None:
            raise RuntimeError("device engine not attached")
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

    def query_range(self, q: str, start_ms: int, end_ms: int, step_ms: int,
                    nocache: bool) -> bytes:
        """The raw answer; b"" where the server refused (HTTP error)."""
        params = dict(query=q, start=start_ms // 1000, end=end_ms // 1000,
                      step=step_ms // 1000)
        if nocache:
            params["nocache"] = "1"
        try:
            return self.get("/api/v1/query_range", **params)
        except urllib.error.HTTPError:
            return b""


# The bulk ends this long before the wall clock.  A refresh mix moves
# simulated time one query step a tick, some hundred times faster than
# the wall clock: anchored here, every tick of a window stays in the
# past (a 50 s window at 5 ticks a second and the warm-up's pre-roll move
# it 6 h), as a replay of half a day ago, and nothing is future-dated.
BULK_AGE_MS = 12 * 3_600_000
# ... and a window that would come within this of the wall clock fails
# the run, loudly, before it measures a server answering for the future
WALL_MARGIN_MS = 10 * 60_000


class Dataset:
    """The deployment's samples, anchored BULK_AGE_MS behind the wall
    clock (a literal timestamp would sooner or later fall out of
    retention), and the grid the queries walk.  Keeps every sample it
    handed out: the reference reads them, never the program's storage."""

    def __init__(self, cfg: dict, seed: int, now_ms: int):
        self.latest = now_ms - WALL_MARGIN_MS
        now_ms -= BULK_AGE_MS
        self.cfg = cfg
        self.step = int(cfg["query_step_s"] * 1000)
        self.window = int(cfg["window_s"] * 1000)
        self.scrape = int(cfg["scrape_interval_s"] * 1000)
        n_samples = int(cfg["range_h"] * 3_600_000) // self.scrape
        jitter = int(cfg["jitter_s"] * 1000)
        self.rng = np.random.default_rng(seed)
        self.gen = load_module("deployments", cfg["deployment"]).Deployment(cfg)
        self.labels = self.gen.labels()
        self.keys = [(l["__name__"] + "{" + ",".join(
            f'{k}="{v}"' for k, v in sorted(l.items()) if k != "__name__")
            + "}").encode() for l in self.labels]
        span = (n_samples - 1) * self.scrape
        self.t_start = (now_ms - span) // self.step * self.step
        # the first window ends BEYOND every bulk sample, jitter included,
        # so fresh tails never interleave with the bulk
        self.end = self.t_start + -(-(span + jitter) // self.step) * self.step
        self.duration = span // self.step * self.step - self.window
        self.ts, self.vals = self.gen.scrapes(
            self.rng, self.t_start - self.scrape, n_samples)
        self.tails = []

    @property
    def start(self) -> int:
        return self.end - self.duration

    def advance(self, steps: int = 1):
        """Move the window `steps` query steps on and make their scrapes."""
        span = steps * self.step
        if self.end + span > self.latest:
            raise RuntimeError("the window's simulated time has caught up "
                               "with the wall clock: raise BULK_AGE_MS")
        self.end += span
        tail = self.gen.scrapes(self.rng, self.end - span,
                                span // self.scrape)
        self.tails.append(tail)
        return tail

    def text(self, ts2: np.ndarray, vals2: np.ndarray) -> bytes:
        """Prometheus text exposition with timestamps, one line a sample."""
        rows = []
        for key, vs, tss in zip(self.keys, vals2.astype(np.int64).tolist(),
                                ts2.tolist()):
            k = key.decode()
            rows.extend(f"{k} {v} {t}" for v, t in zip(vs, tss))
        return ("\n".join(rows) + "\n").encode()

    def snapshot(self, n_tails: int):
        """Every sample handed out up to the n_tails-th tail."""
        tails = self.tails[:n_tails]
        return (np.concatenate([self.ts] + [t for t, _ in tails], axis=1),
                np.concatenate([self.vals] + [v for _, v in tails], axis=1))


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
        ts, vals = data.snapshot(r["n_tails"])
        grid = np.arange(r["start"], r["end"] + 1, data.step, dtype=np.int64)
        ast = reference.parse(r["query"])
        kind, labels, ref = reference.evaluate(ast, data.labels, ts, vals, grid)
        if round_rollup is not None:
            got = control_answer(*reference.evaluate(
                ast, data.labels, ts, vals, grid, round_rollup))
        else:
            ok, got = compare.parse_answer(r["body"], r["start"], r["end"],
                                           data.step)
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
    """What one asked query needs moved, for the roofline: the real
    samples of the matched series in the fetched range (start - window,
    end], and the values of its answer."""
    ast = reference.parse(asked["query"])
    idx = reference.select(data.labels, *reference.selector(ast))
    lo, hi = asked["start"] - data.window, asked["end"]
    samples = sum(int(((ts[idx] > lo) & (ts[idx] <= hi)).sum())
                  for ts in [data.ts] + [t for t, _ in
                                         data.tails[:asked["n_tails"]]])
    rows = len(idx)
    if ast[0] == "sum":
        rows = len({tuple(data.labels[i].get(k) for k in ast[1]) for i in idx})
    elif ast[0] == "topk":
        rows = min(ast[1], rows)
    elif ast[0] == "hq":
        rows = 1
    steps = (asked["end"] - asked["start"]) // data.step + 1
    return {"samples": samples, "out_values": rows * steps}
