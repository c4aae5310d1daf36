"""A histogram panel on the served device path (ISSUE 30, `histo8k`):

(a) `tf_histogram_quantile`'s array pass over [groups, buckets, T] against
    the benchmark's plain reference (`benchmark/reference.py`, loaded by
    path: it imports nothing of the program) and against hand values,
    one case an edge;
(b) both cells' queries over HTTP `query_range` with the device backend
    on, on the benchmark's own generator at 8 instances x 12 buckets,
    against `reference.evaluate`;
(c) the routing guard: a refresh of `histogram_quantile(phi, aggr)` is
    served from the resident window as the bare aggregate's is;
(d) the phase `eval:transform` is a member of the query family and the
    phases still partition the root's wall.
"""

import importlib.util
import os
import time

import numpy as np
import pytest

from tests.apptest_helpers import REPO, Client
from victoriametrics_tpu import native
from victoriametrics_tpu.models import tile_cache as tclib
from victoriametrics_tpu.query import rollup_result_cache as rrc
from victoriametrics_tpu.query.exec import exec_query
from victoriametrics_tpu.query.transform_funcs import tf_histogram_quantile
from victoriametrics_tpu.query.types import EvalConfig, new_series
from victoriametrics_tpu.utils import flightrec
from victoriametrics_tpu.utils import metrics as metricslib


def _by_path(*parts):
    path = os.path.join(REPO, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "histo_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _by_path("reference.py")
inf, nan = np.inf, np.nan


# -- (a) the transform --------------------------------------------------------

def _buckets(les, counts, extra=()):
    """Bucket series of one group: counts [B, T] cumulative along B."""
    return [new_series(np.asarray(c, dtype=np.float64),
                       labels=[(b"le", le.encode())] + list(extra))
            for le, c in zip(les, counts)]


def _hq(phi, series, *more):
    return tf_histogram_quantile(None, [phi, series, *more])


LES = ["0.1", "0.5", "1", "+Inf"]
F_LES = np.array([0.1, 0.5, 1.0, inf])
# columns: a plain one; an all-NaN one; a zero total; the rank in +Inf;
# a flat bucket at the rank (c_hi <= c_lo); the rank in the lowest bucket
M = np.array([[2.0, nan, 0.0, 1.0, 0.0, 9.0],
              [6.0, nan, 0.0, 2.0, 0.0, 9.0],
              [9.0, nan, 0.0, 3.0, 0.0, 10.0],
              [10.0, nan, 0.0, 10.0, 5.0, 10.0]])

EDGES = {
    "plain": (0.5, M[:, :1], [0.1 + 0.4 * (5 - 2) / (6 - 2)]),
    "all_nan_column": (0.9, M[:, 1:2], [nan]),
    "zero_total": (0.9, M[:, 2:3], [nan]),
    "inf_bucket_answers_highest_finite_bound": (0.9, M[:, 3:4], [1.0]),
    "flat_bucket_c_hi_le_c_lo": (0.0, M[:, 4:5], [0.1]),
    "lowest_bucket_from_zero": (0.45, M[:, 5:6], [0.1 * 4.5 / 9.0]),
    "phi_below_0": (-0.1, M, [-inf, nan, nan, -inf, -inf, -inf]),
    "phi_above_1": (1.5, M, [inf, nan, nan, inf, inf, inf]),
}


@pytest.mark.parametrize("case", sorted(EDGES))
def test_an_edge_equals_the_reference_and_the_hand_value(case):
    phi, m, want = EDGES[case]
    (row,) = _hq(phi, _buckets(LES, m))
    np.testing.assert_allclose(row.values, want, rtol=1e-12, atol=0)
    if 0 <= phi <= 1:
        np.testing.assert_allclose(
            row.values, reference._histogram_quantile(phi, F_LES, m),
            rtol=1e-12, atol=0)


def test_phi_given_as_a_series_varies_by_step():
    phis = np.array([0.5, 0.9, -1.0, 2.0, 0.0, 0.45])
    (row,) = _hq([new_series(phis)], _buckets(LES, M))
    want = [reference._histogram_quantile(p, F_LES, M[:, j:j + 1])[0]
            for j, p in enumerate(phis)]
    want[2], want[3] = nan, inf         # the zero total wins over phi < 0
    np.testing.assert_allclose(row.values, want, rtol=1e-12, atol=0)


def test_duplicate_le_is_merged():
    """le="1" and le="1.0" are one bucket from two scrapes: summed."""
    series = _buckets(["0.1", "1", "1.0", "+Inf"],
                      [[2.0], [3.0], [4.0], [10.0]])
    (row,) = _hq(0.5, series)
    merged = reference._histogram_quantile(
        0.5, np.array([0.1, 1.0, inf]), np.array([[2.0], [7.0], [10.0]]))
    np.testing.assert_allclose(row.values, merged, rtol=1e-12)
    np.testing.assert_allclose(row.values, [0.1 + 0.9 * 3 / 5], rtol=1e-12)


def test_vmrange_input_is_converted_first():
    series = [new_series(np.array([c]), labels=[(b"vmrange", r)])
              for r, c in ((b"0...0.1", 2.0), (b"0.1...0.5", 4.0),
                           (b"0.5...1", 4.0))]
    (row,) = _hq(0.5, series)
    want = reference._histogram_quantile(
        0.5, np.array([0.1, 0.5, 1.0, inf]),
        np.array([[2.0], [6.0], [10.0], [10.0]]))
    np.testing.assert_allclose(row.values, want, rtol=1e-12)
    np.testing.assert_allclose(row.values, [0.1 + 0.4 * 3 / 4], rtol=1e-12)


def test_bounds_label_rows():
    lower, upper, row = _hq(0.5, _buckets(LES, M), "bound")
    assert lower.metric_name.get_label(b"bound") == b"lower"
    assert upper.metric_name.get_label(b"bound") == b"upper"
    np.testing.assert_array_equal(row.values, _hq(0.5, _buckets(LES, M))[0]
                                  .values)
    # the edges of the bucket the answer lies in, none where there is no
    # answer; an answer AT a bound (the +Inf columns answer 1) has that
    # bound above it
    np.testing.assert_array_equal(lower.values, [0.1, nan, nan, 0.5, 0.5, 0.0])
    np.testing.assert_array_equal(upper.values, [0.5, nan, nan, 1.0, 1.0, 0.1])


def test_groups_go_through_as_one_block_and_equal_the_reference():
    """17 groups of the 12 default buckets with NaN holes, plus a group
    with bounds of its own: every row against the reference's loop."""
    rng = np.random.default_rng(30)
    les = ["0.005", "0.01", "0.025", "0.05", "0.1", "0.25", "0.5", "1",
           "2.5", "5", "10", "+Inf"]
    f_les = np.array([float(x) for x in les])
    series, want = [], {}
    for g in range(17):
        m = np.cumsum(rng.integers(0, 40, (12, 50)), axis=0).astype(float)
        m[:, rng.integers(0, 50, 3)] = nan
        m[rng.random(m.shape) < 0.02] = nan
        series += _buckets(les, m, [(b"job", b"job-%d" % g)])
        want[b"job-%d" % g] = reference._histogram_quantile(0.99, f_les, m)
    odd = np.cumsum(rng.integers(1, 9, (3, 50)), axis=0).astype(float)
    series += _buckets(["1", "2", "4"], odd, [(b"job", b"odd")])
    want[b"odd"] = reference._histogram_quantile(
        0.99, np.array([1.0, 2.0, 4.0]), odd)
    rng.shuffle(series)
    points = metricslib.REGISTRY.counter("vm_histogram_quantile_points_total")
    p0 = points.get()
    rows = _hq(0.99, series)
    assert points.get() - p0 == 18 * 50
    assert sorted(r.metric_name.get_label(b"job") for r in rows) == \
        sorted(want)
    for r in rows:
        assert r.metric_name.get_label(b"le") is None
        np.testing.assert_allclose(
            r.values, want[r.metric_name.get_label(b"job")], rtol=1e-12,
            atol=0)


# -- the histogram deployment, served -----------------------------------------

STEP = 60_000
CFG = dict(metric="latency_bucket", series=96, instances=8, jobs=4,
           buckets=["0.005", "0.01", "0.025", "0.05", "0.1", "0.25", "0.5",
                    "1", "2.5", "5", "10", "+Inf"],
           rate_min=20, rate_max=200, latency_median_s=0.02,
           latency_median_growth=1.1, latency_sigma=1.0,
           scrape_interval_s=15, jitter_s=2)
PANELS = {
    "histo8k.refresh":
        "histogram_quantile(0.99, sum by (le)(rate(latency_bucket[5m])))",
    "histo8k.services":
        "histogram_quantile(0.99, sum by (le, job)(rate(latency_bucket[5m])))",
}
FAMILY = "vm_query_phase_seconds_total{"
WALL = "vm_query_wall_seconds_total"


def _metrics() -> dict:
    out = {}
    for line in metricslib.REGISTRY.write_prometheus().splitlines():
        if line.startswith((FAMILY, WALL,
                            "vm_histogram_quantile_points_total")):
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


def _text(labels, ts, vals) -> bytes:
    rows = []
    for l, tss, vs in zip(labels, ts.tolist(), vals.astype(np.int64).tolist()):
        key = l["__name__"] + "{" + ",".join(
            f'{k}="{v}"' for k, v in sorted(l.items()) if k != "__name__") + "}"
        rows.extend(f"{key} {v} {t}" for v, t in zip(vs, tss))
    return ("\n".join(rows) + "\n").encode()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The benchmark's histogram generator at 8 instances x 12 buckets,
    an hour of scrapes ending 12 h ago (as the benchmark's bulk does),
    imported and queried over HTTP with the device engine attached."""
    if not native.available():
        pytest.skip("needs native lib")
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.httpapi.server import HTTPServer
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    from victoriametrics_tpu.storage.storage import Storage
    gen = _by_path("deployments", "histogram.py").Deployment(CFG)
    rng = np.random.default_rng(3_000_000_019)
    t0 = (int(time.time() * 1000) - 13 * 3_600_000) // STEP * STEP
    s = Storage(str(tmp_path_factory.mktemp("histo") / "s"))
    srv = HTTPServer("127.0.0.1", 0)
    PrometheusAPI(s, tpu_engine=TPUEngine(min_series=2)).register(srv)
    srv.start()
    c = Client(srv.port)
    data = {"labels": gen.labels(), "ts": np.empty((96, 0), np.int64),
            "vals": np.empty((96, 0)), "end": t0}

    def ingest(k: int) -> int:
        ts, vals = gen.scrapes(rng, data["end"] - 15_000, k)
        code, body = c.post("/api/v1/import/prometheus",
                            _text(data["labels"], ts, vals))
        assert code in (200, 204), body
        c.force_flush()
        data["ts"] = np.hstack([data["ts"], ts])
        data["vals"] = np.hstack([data["vals"], vals])
        data["end"] += k * 15_000
        return data["end"]

    ingest(240)
    try:
        yield c, ingest, data
    finally:
        srv.stop()
        s.close()


def _ask(c, q: str, start: int, end: int) -> dict:
    """One query_range; returns once the request's root has closed, so
    its counters have landed."""
    wall0 = _metrics()[WALL]
    res = c.query_range(q, start / 1e3, end / 1e3, STEP // 1000)
    assert res["status"] == "success" and not res.get("isPartial")
    for _ in range(2000):
        if _metrics()[WALL] > wall0:
            return res
        time.sleep(0.005)
    raise AssertionError("the request's root never closed")


@pytest.mark.parametrize("cell", sorted(PANELS))
def test_a_cells_query_over_http_equals_the_reference(served, cell):
    """Tolerance 1e-9: on the CPU the tiles are float64, so what is left
    is the order of a group's sum (1e-16 a term) times the transform's
    own amplification, (le_hi - le_lo) / q over the share of the counts
    in the rank's bucket (as low as 1 %): some 1e-13; 1e-9 leaves room
    and still fails a float32 evaluation (1e-6, benchmark's control
    1e-2)."""
    c, ingest, data = served
    end = ingest(4)
    start = end - 40 * STEP
    for _ in range(2):          # cold, then from the resident window
        res = _ask(c, PANELS[cell], start, end)
    grid = np.arange(start, end + 1, STEP, dtype=np.int64)
    _, labels, want = reference.evaluate(
        reference.parse(PANELS[cell]), data["labels"], data["ts"],
        data["vals"], grid)
    got = {tuple(sorted(r["metric"].items())):
           {round(t * 1000): float(v) for t, v in r["values"]}
           for r in res["data"]["result"]}
    assert len(got) == len(labels) == (1 if cell.endswith("refresh") else 4)
    for l, row in zip(labels, want):
        mine = got[tuple(sorted(l.items()))]
        assert sorted(mine) == [int(t) for t in grid[~np.isnan(row)]]
        assert not np.isnan(row[-1])        # the fresh scrapes are seen
        np.testing.assert_allclose([mine[int(t)] for t in grid], row,
                                   rtol=1e-9, atol=0)


def test_eval_transform_is_a_member_and_the_phases_partition_the_wall(served):
    """(d): the family holds `eval:transform` from import on; one served
    histogram panel ticks it, its deltas with the other members' sum to
    the root's wall, and the points counter counts groups x steps."""
    assert "eval:transform" in flightrec.QUERY_PHASES
    member = FAMILY + 'phase="eval:transform"}'
    c, ingest, data = served
    end = ingest(4)
    _ask(c, PANELS["histo8k.services"], end - 40 * STEP, end - STEP)
    m0 = _metrics()
    assert member in m0
    _ask(c, PANELS["histo8k.services"], end - 40 * STEP, end)
    m1 = _metrics()
    fam = {k: v - m0[k] for k, v in m1.items() if k.startswith(FAMILY)}
    wall = m1[WALL] - m0[WALL]
    assert wall > 0 and fam[member] > 0
    assert sum(fam.values()) == pytest.approx(wall, rel=0.01)
    assert fam[FAMILY + 'phase="eval:other"}'] > 0
    assert m1["vm_histogram_quantile_points_total"] - \
        m0["vm_histogram_quantile_points_total"] == 4 * 41


# -- (c) the routing guard ----------------------------------------------------

SCRAPE = 15_000
N_INST, R_LES = 16, ["0.1", "0.5", "1", "+Inf"]
Q_AGGR = "sum by (le)(rate(hres_bucket[5m]))"
Q_HQ = f"histogram_quantile(0.9, {Q_AGGR})"


def _labels():
    return [{"__name__": "hres_bucket", "i": str(i), "le": le}
            for i in range(N_INST) for le in R_LES]


def _scrapes(rng, last, t_from: int, k: int):
    """k scrapes of every bucket series after t_from, ending at the wall
    clock: [(labels, ts, value)] rows; `last` [N_INST, B] is carried."""
    rows = []
    labels = _labels()
    for i in range(N_INST):
        ts = t_from + (np.arange(k, dtype=np.int64) + 1) * SCRAPE - \
            rng.integers(0, 2000, k)
        hits = np.cumsum(np.cumsum(rng.integers(0, 30, (len(R_LES), k)),
                                   axis=0), axis=1) + last[i][:, None]
        last[i] = hits[:, -1]
        for b in range(len(R_LES)):
            rows.extend(zip([labels[i * len(R_LES) + b]] * k, ts.tolist(),
                            hits[b].astype(float).tolist()))
    return rows


def _refreshes(path, q: str, nocache_check: bool = False):
    """A cold eval, then three refreshes at the wall clock (now = end), a
    step of fresh scrapes each -> (rows of each refresh, resident-window
    hits, bytes uploaded by each refresh)."""
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    from victoriametrics_tpu.storage.storage import Storage
    s = Storage(str(path))
    try:
        rng = np.random.default_rng(5)
        last = np.zeros((N_INST, len(R_LES)))
        n = 240
        t0 = (int(time.time() * 1000) - n * SCRAPE) // STEP * STEP
        s.add_rows(_scrapes(rng, last, t0, n))
        s.force_flush()
        end = t0 + n * SCRAPE
        rrc.GLOBAL.reset()
        engine = TPUEngine(min_series=4)
        api = PrometheusAPI(s, engine)
        dur = 30 * STEP
        kw = dict(step=STEP, storage=s, tpu=engine)
        api._exec_range_cached(EvalConfig(start=end - dur, end=end, **kw),
                               q, end)
        hits = metricslib.REGISTRY.counter("vm_device_window_cache_hits_total")
        hits0 = hits.get()
        out, uploads = [], []
        for _ in range(3):
            s.add_rows(_scrapes(rng, last, end, 4))
            end += STEP
            up = tclib.bytes_uploaded()
            rows = api._exec_range_cached(
                EvalConfig(start=end - dur, end=end, **kw), q, end)
            uploads.append(tclib.bytes_uploaded() - up)
            out.append({r.metric_name.marshal(): np.asarray(r.values)
                        for r in rows})
            if nocache_check:
                cold = exec_query(EvalConfig(start=end - dur, end=end, **kw,
                                             disable_cache=True), q)
                _same(out[-1], {r.metric_name.marshal(): np.asarray(r.values)
                                for r in cold})
        return out, hits.get() - hits0, uploads
    finally:
        s.close()


def _same(got: dict, want: dict) -> None:
    """rtol 1e-12, as test_device_residency holds the resident path to
    its oracles: XLA orders a group's sum by the grid's shape."""
    assert set(got) == set(want) and got
    for k in got:
        gaps = np.isnan(got[k])
        np.testing.assert_array_equal(gaps, np.isnan(want[k]))
        assert not gaps[-1]
        np.testing.assert_allclose(got[k][~gaps], want[k][~gaps], rtol=1e-12)


def test_a_histogram_refresh_is_served_from_the_resident_window(
        tmp_path, monkeypatch):
    """THE routing guard: three refreshes tick the resident-window hit
    counter three times and upload no more than the bare aggregate's
    refresh does (before: 0 hits and fresh tiles of the suffix's whole
    fetch window, 4x the bytes); `nocache=1` and VM_DEVICE_RESIDENT=0
    still answer the same."""
    _, aggr_hits, aggr_uploads = _refreshes(tmp_path / "aggr", Q_AGGR)
    assert aggr_hits == 3 and min(aggr_uploads) > 0
    got, hq_hits, hq_uploads = _refreshes(tmp_path / "hq", Q_HQ,
                                          nocache_check=True)
    assert hq_hits == 3, "the transform's refresh left the resident window"
    assert hq_uploads == aggr_uploads
    monkeypatch.setenv("VM_DEVICE_RESIDENT", "0")
    want, oracle_hits, _ = _refreshes(tmp_path / "oracle", Q_HQ)
    assert oracle_hits == 0
    for g, w in zip(got, want):
        _same(g, w)
