"""The four-chip deployment on the CPU's virtual devices (ISSUE 34,
`dash32k.refresh4`): vmsingle served over HTTP from a 4 x 1 series mesh,
in the dtype regime of the chip (float32 rebased tiles, so the rebase
offsets `v0` ride the mesh step as they do there).

(a) the refresh loop of the benchmark's mixes - a tick imports one query
    step of fresh scrapes, moves the window a step and asks - past one
    slide of the resident window, at a series count that needs padding
    rows: every answer against the benchmark's plain reference
    (`benchmark/reference.py` through `benchmark/compare.py`, loaded by
    path) at the cell's own `rel_err`, and against the one-device
    engine's answer on the same seed; for the aggregate panel, the
    histogram panel and `topk`;
(b) a warm refresh sends the tick's tail up and nothing else;
(c) the mesh step's first call books `device:compile`, its second
    `device:execute`.

The compile of the donated `compact_tile` for the 2 x 2 topology is with
the other chip compiles (`tests/test_chip_compile.py`).
"""

import importlib.util
import os
import time

import numpy as np
import pytest

from tests.apptest_helpers import REPO, Client
from victoriametrics_tpu import native
from victoriametrics_tpu.models import tile_cache as tclib
from victoriametrics_tpu.utils import metrics as metricslib

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs native lib")


def _by_path(*parts):
    path = os.path.join(REPO, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "mesh_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _by_path("reference.py")
compare = _by_path("compare.py")

STEP, SCRAPE = 60_000, 15_000
# the bulk: 38 min of scrapes under a 28 min panel, so the cold tile holds
# some 150 columns of 192 and its headroom runs out after ten ticks
BULK_SCRAPES, PANEL_STEPS = 152, 28
COUNTERS = dict(deployment="counters", metric="http_requests_total",
                series=150, instances=10, jobs=17, scrape_interval_s=15,
                jitter_s=2, max_increment=50)
HISTOGRAM = dict(deployment="histogram", metric="latency_bucket", series=77,
                 instances=7, jobs=4,
                 buckets=["0.005", "0.01", "0.025", "0.05", "0.1", "0.25",
                          "0.5", "1", "2.5", "5", "+Inf"],
                 rate_min=20, rate_max=200, latency_median_s=0.02,
                 latency_median_growth=1.1, latency_sigma=1.0,
                 scrape_interval_s=15, jitter_s=2)
# (deployment, query, nocache as its cell's mix sends it, the rel_err limit
# of the configuration that asks it on the chip)
PANELS = {
    "aggregate": (COUNTERS,
                  "sum by (instance)(rate(http_requests_total[5m]))",
                  False, 5e-5),
    "histogram": (HISTOGRAM,
                  "histogram_quantile(0.99, sum by (le)"
                  "(rate(latency_bucket[5m])))", False, 2e-4),
    "topk": (COUNTERS, "topk(10, rate(http_requests_total[5m]))", True,
             5e-5),
}
COMPACTIONS = "vm_device_window_compactions_total"
LAUNCHES = 'vm_device_fused_launches_total{path="%s"}'


def _metric(name: str) -> float:
    for line in metricslib.REGISTRY.write_prometheus().splitlines():
        if line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(f"{name} is not exported")


class Served:
    """vmsingle as `benchmark/harness.Server` builds it (apps/vmsingle's
    own build(), served from a thread on a loopback port), but with the
    device engine over the first `n_dev` of the suite's 8 host devices
    instead of all of them, and float32 tiles as on a TPU."""

    def __init__(self, data_dir: str, n_dev: int):
        import jax

        from victoriametrics_tpu.apps import vmsingle
        from victoriametrics_tpu.parallel.mesh import make_mesh
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        args = vmsingle.parse_flags([
            f"-storageDataPath={data_dir}", "-httpListenAddr=127.0.0.1:0",
            "-search.maxQueryDuration=300s"])
        self.storage, self.srv, self.api = vmsingle.build(args)
        mesh = make_mesh(jax.devices()[:n_dev]) if n_dev > 1 else None
        self.engine = self.api.tpu = TPUEngine(
            mesh=mesh, value_dtype=np.float32, min_series=2)
        assert self.engine.series_shards() == n_dev
        self.srv.start()
        self.client = Client(self.srv.port)

    def stop(self):
        self.srv.stop()
        self.storage.close()


class Panel:
    """One open panel over a deployment's generator, anchored 12 h behind
    the wall clock as the benchmark's bulk is; keeps every sample it
    handed out, for the reference."""

    def __init__(self, served: Served, cfg: dict, seed: int):
        self.c = served.client
        self.gen = _by_path("deployments", cfg["deployment"] + ".py") \
            .Deployment(cfg)
        self.rng = np.random.default_rng(seed)
        self.labels = self.gen.labels()
        self.keys = [l["__name__"] + "{" + ",".join(
            f'{k}="{v}"' for k, v in sorted(l.items()) if k != "__name__")
            + "}" for l in self.labels]
        n = len(self.labels)
        self.ts, self.vals = np.empty((n, 0), np.int64), np.empty((n, 0))
        self.end = (int(time.time() * 1000) - 13 * 3_600_000) // STEP * STEP
        self.ingest(BULK_SCRAPES)
        # the first panel ends beyond every bulk sample, jitter included
        self.end += STEP

    def ingest(self, k: int) -> None:
        """k fresh scrapes of every series after the panel's end, which
        moves on by their span (the last may lie up to the jitter beyond
        it: the next refresh's tail brings it, as in the benchmark)."""
        ts, vals = self.gen.scrapes(self.rng, self.end, k)
        rows = [f"{key} {v} {t}" for key, vs, tss in
                zip(self.keys, vals.astype(np.int64).tolist(), ts.tolist())
                for v, t in zip(vs, tss)]
        code, body = self.c.post("/api/v1/import/prometheus",
                                 ("\n".join(rows) + "\n").encode())
        assert code in (200, 204), body
        self.ts = np.hstack([self.ts, ts])
        self.vals = np.hstack([self.vals, vals])
        self.end += k * SCRAPE

    def ask(self, q: str, nocache: bool):
        """-> (the served answer parsed as the benchmark parses it, the
        reference's (kind, labels, values) on the samples acknowledged)."""
        start = self.end - PANEL_STEPS * STEP
        params = dict(query=q, start=start // 1000, end=self.end // 1000,
                      step=STEP // 1000)
        if nocache:
            params["nocache"] = "1"
        code, body = self.c.get("/api/v1/query_range", **params)
        assert code == 200, body
        ok, got = compare.parse_answer(body, start, self.end, STEP)
        assert ok, body[:300]
        grid = np.arange(start, self.end + 1, STEP, dtype=np.int64)
        return got, reference.evaluate(reference.parse(q), self.labels,
                                       self.ts, self.vals, grid)


def _refresh(tmp_path, n_dev: int, panel: str, seed: int, ticks=None):
    """The panel opened on an `n_dev`-device server and refreshed until the
    resident window has slid once and two ticks more (or `ticks` times).
    -> [(answer, reference)] a tick, the counters' growth."""
    cfg, q, nocache, _ = PANELS[panel]
    served = Served(str(tmp_path / f"s{n_dev}"), n_dev)
    try:
        before = {n: _metric(n) for n in
                  (COMPACTIONS, LAUNCHES % "mesh", LAUNCHES % "single")}
        p = Panel(served, cfg, seed)
        out = [p.ask(q, nocache)]
        slid_at = None
        while len(out) <= (40 if ticks is None else ticks):
            p.ingest(STEP // SCRAPE)
            out.append(p.ask(q, nocache))
            if slid_at is None and _metric(COMPACTIONS) > before[COMPACTIONS]:
                slid_at = len(out)
            if ticks is None and slid_at is not None and \
                    len(out) >= slid_at + 2:
                break
        grew = {n: _metric(n) - v for n, v in before.items()}
        return out, grew
    finally:
        served.stop()


@pytest.mark.parametrize("panel", sorted(PANELS))
def test_four_devices_answer_as_the_reference_and_as_one(tmp_path, panel):
    """(a).  The series count needs padding rows (150 and 77 over 4
    devices); the fresh scrapes are in every answer's newest step
    (nan_mismatch 0: visible whichever device holds the row); the mesh's
    answer and one device's differ by float32 summation order only."""
    _, _, nocache, limit = PANELS[panel]
    resident = not nocache
    four, grew4 = _refresh(tmp_path, 4, panel, 3_400_000_019,
                           None if resident else 5)
    one, grew1 = _refresh(tmp_path, 1, panel, 3_400_000_019, len(four) - 1)
    assert len(four) == len(one)
    if resident:
        assert grew4[COMPACTIONS] >= 1 and grew1[COMPACTIONS] >= 1, \
            f"no slide in {len(four)} ticks"
        # every fused launch of an engine took that engine's path
        assert grew4[LAUNCHES % "mesh"] >= len(four)
        assert grew4[LAUNCHES % "single"] == 0
        assert grew1[LAUNCHES % "single"] >= len(one)
        assert grew1[LAUNCHES % "mesh"] == 0
    for (got4, (kind, labels, want)), (got1, _) in zip(four, one):
        for got in (got4, got1):
            n = compare.compare(kind, got, labels, want)
            assert n["rel_err"] <= limit, n
            assert n["series_mismatch"] == 0 and n["nan_mismatch"] == 0, n
            assert n["values"] >= 1
        assert sorted(got4) == sorted(got1)
        for key, row in got4.items():
            np.testing.assert_array_equal(np.isnan(row), np.isnan(got1[key]))
            np.testing.assert_allclose(row, got1[key], rtol=limit, atol=0,
                                       equal_nan=True)


def test_a_warm_refresh_uploads_its_tail_and_nothing_else(tmp_path):
    """(b).  Group ids and v0 were placed with the resident tile; a warm
    tick's upload is its staged tail, to the byte: the tile's 152 padded
    rows x 8 padded columns of int32 timestamps and float64 staged values,
    and the rows' int32 counts - what one device is sent."""
    cfg, q, nocache, _ = PANELS["aggregate"]
    served = Served(str(tmp_path / "s"), 4)
    try:
        p = Panel(served, cfg, 3_400_000_023)
        p.ask(q, nocache)
        for _ in range(2):
            p.ingest(STEP // SCRAPE)
            p.ask(q, nocache)
        up0, hits0 = tclib.bytes_uploaded(), _metric(
            "vm_device_window_cache_hits_total")
        n = 3
        for _ in range(n):
            p.ingest(STEP // SCRAPE)
            p.ask(q, nocache)
        assert _metric("vm_device_window_cache_hits_total") - hits0 == n
        rows = -(-cfg["series"] // 4) * 4
        assert tclib.bytes_uploaded() - up0 == n * (rows * 8 * (4 + 8)
                                                    + rows * 4)
        # ... because what a query needs per series lives where the rows
        # live: the state's group ids and the tile's v0, padded as the
        # tile is and sharded by the rule table (an argument placed
        # otherwise would be re-sent, or re-sharded, by every launch)
        from victoriametrics_tpu.parallel.partition import sharding_for
        (state,) = [v for k, v in
                    served.engine.window_cache()._entries.items()
                    if k[0] == "roll-aggr"]
        rt, gids_dev = state[0], state[1]
        mesh = served.engine.mesh
        assert gids_dev.shape == (rows,) == rt.tiles[2].shape
        assert gids_dev.sharding == sharding_for(mesh, "group_ids", 1)
        v0 = rt.tiles[3]
        assert v0.dev.shape == (rows,) and v0.dev.dtype == np.float32
        assert v0.dev.sharding == sharding_for(mesh, "v0", 1)
        placed = v0.dev
        p.ingest(STEP // SCRAPE)
        p.ask(q, nocache)
        assert state[0].tiles[3].dev is placed      # handed on by the append
    finally:
        served.stop()


def test_the_mesh_steps_first_call_books_a_compile(tmp_path):
    """(c).  timed_kernel_call sees the mesh step's own jit: a shape it
    has not met is booked as device:compile, the same shape again as
    device:execute."""
    import jax

    from victoriametrics_tpu.ops.device_rollup import TS_PAD, pack_series
    from victoriametrics_tpu.ops.rollup_np import RollupConfig
    from victoriametrics_tpu.parallel.mesh import make_mesh
    from victoriametrics_tpu.parallel.partition import shard_put
    from victoriametrics_tpu.query.tpu_engine import (
        TPUEngine, _kernel_histogram, place_series_vector,
        run_fused_on_tiles)
    engine = TPUEngine(mesh=make_mesh(jax.devices()[:4]))
    rng = np.random.default_rng(34)
    pairs = [(np.arange(40, dtype=np.int64) * SCRAPE,
              np.cumsum(rng.integers(0, 50, 40)).astype(np.float64))
             for _ in range(10)]
    ts, vals, counts = pack_series(pairs, 0, n_pad=64)
    tiles = (shard_put(engine.mesh, "ts", ts, TS_PAD),
             shard_put(engine.mesh, "values", vals),
             shard_put(engine.mesh, "counts", counts), None)
    assert tiles[0].shape[0] == 12              # 10 rows padded to 4 x 3
    # a grid no other test of this process asks the mesh step for
    cfg = RollupConfig(start=300_000, end=300_000 + 7 * 34_000, step=34_000,
                       window=300_000)
    gids = place_series_vector(engine, "group_ids",
                               (np.arange(10) % 3).astype(np.int32))
    count = {ph: _kernel_histogram("sharded_rollup_aggregate", ph)
             for ph in ("compile", "execute")}
    c0, e0 = count["compile"].get_count(), count["execute"].get_count()
    first = run_fused_on_tiles(engine, "sum", "rate", tiles, gids, 3, cfg)
    assert (count["compile"].get_count() - c0,
            count["execute"].get_count() - e0) == (1, 0)
    second = run_fused_on_tiles(engine, "sum", "rate", tiles, gids, 3, cfg)
    assert (count["compile"].get_count() - c0,
            count["execute"].get_count() - e0) == (1, 1)
    np.testing.assert_array_equal(first, second)
    assert first.shape == (3, 8) and np.isfinite(first[:, 1:]).all()


def test_the_launch_counters_and_the_shards_gauge_are_exported():
    """Both members of vm_device_fused_launches_total stand in /metrics
    whichever path has run (the benchmark's ratio needs the family, and
    reads 100 only if `single` stays put), and vm_device_series_shards
    says how many ways the newest engine's rows are split."""
    import jax

    from victoriametrics_tpu.parallel.mesh import make_mesh
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    for path in ("mesh", "single"):
        assert _metric(LAUNCHES % path) >= 0
    TPUEngine(mesh=make_mesh(jax.devices()[:4]))
    assert _metric("vm_device_series_shards") == 4
    TPUEngine()
    assert _metric("vm_device_series_shards") == 1
