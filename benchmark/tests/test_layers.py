"""Every layer file BENCHMARK.json names loads, names a reader that is
there, and - where it reads /metrics counters - names series the program
really exports: a misspelt name fails here, on the CPU, not on the chip."""

import json
import os

import pytest

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    PER_LAYER = json.load(f)["per_layer"]


@pytest.fixture(scope="module")
def exported():
    """The series names the program exports before any traffic: every
    module of the served path imported, the query route's own metrics
    made as its first request would."""
    from victoriametrics_tpu.httpapi.server import HTTPServer
    from victoriametrics_tpu.models import tile_cache  # noqa: F401
    from victoriametrics_tpu.query import tpu_engine  # noqa: F401
    from victoriametrics_tpu.storage import storage  # noqa: F401
    from victoriametrics_tpu.utils import metrics
    srv = HTTPServer("127.0.0.1", 0)
    srv._path_metrics("/api/v1/query_range")[1].update(0.5)
    srv.stop()
    return [line.rsplit(" ", 1)[0]
            for line in metrics.REGISTRY.write_prometheus().splitlines()
            if line and not line.startswith("#")]


def _matches(want: str, names: list) -> bool:
    return any(n == want or (want.endswith("{") and n.startswith(want))
               for n in names)


@pytest.mark.parametrize("metric", PER_LAYER, ids=lambda m: m["name"])
def test_layer_file_reads_what_the_program_exports(metric, exported):
    spec = harness.load_json(BENCH, "layers", metric["name"] + ".json")
    reader = harness.load_module("readers", spec["reader"])
    assert callable(reader.read)
    if spec["reader"] != "counter_ratio":
        return  # the trace and client readers: test_xtrace, test_traffic
    args = spec["args"]
    series = args["num"] + (args["den"] if isinstance(args.get("den"), list)
                            else [])
    for want in series:
        assert _matches(want, exported), f"{want!r} is exported by nothing"
    ctx = {"m0": {n: 1.0 for n in exported},
           "m1": {n: 3.0 for n in exported}, "queries": 4}
    value = reader.read(args, ctx)
    # each matched series moved by 2 over the made-up window
    moved = 2.0 * sum(_matches(w, [n]) for w in args["num"] for n in exported)
    den = args.get("den")
    if den == "queries":
        over = 4
    elif den is None:
        over = 1
    else:
        over = 2.0 * sum(_matches(w, [n]) for w in den for n in exported)
    assert value == pytest.approx(moved / over * args.get("scale", 1))


def test_a_misspelt_series_reads_nothing():
    reader = harness.load_module("readers", "counter_ratio")
    ctx = {"m0": {"a_total": 1.0}, "m1": {"a_total": 2.0}, "queries": 1}
    assert reader.read({"num": ["a_totl"], "den": "queries"}, ctx) is None


BENCHMARK = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _reported(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", CELLS)]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_what_its_layer_metrics_move(cell):
    """setup_s and one more end to end, a per-layer metric, and no
    per-layer metric that moves an end-to-end metric the cell lacks."""
    end = {m["name"] for m in _reported(BENCHMARK["end_to_end"], cell)}
    assert "setup_s" in end and len(end) >= 2
    layers = _reported(BENCHMARK["per_layer"], cell)
    assert layers
    for m in layers:
        assert m["moves"] in end, (m["name"], m["moves"])


def test_the_tail_is_end_to_end_only_where_it_is_steady():
    """query_p90_ms is the tail of some 160 queries in the 32k cells, of
    which the ticks that meet background work are a tenth to a sixth: the
    90th percentile lies on the edge of the slow mode and swings with it
    (PERF.md section 2).  There the same number is read per layer; and in
    dash8k.explore_live, whose window holds some 123 queries, six of
    them over 2 s (PR 37: spreads 0.091 and 0.106)."""
    (p90,) = [m for m in BENCHMARK["end_to_end"]
              if m["name"] == "query_p90_ms"]
    (tail,) = [m for m in BENCHMARK["per_layer"]
               if m["name"] == "query_tail_p90_ms"]
    big = [w["name"] for w in BENCHMARK["workloads"]
           if w["config"].startswith("dash32k")] + ["dash8k.explore_live"]
    # ... and in dash8k-ha2.refresh, whose window holds six queries of
    # 9.5 s (PR 38), which reports no percentile but its median
    big.append("dash8k-ha2.refresh")
    # ... and in histo8k.services, whose percentiles the check of PR 38
    # read 11-23 % apart from run to run on its machines, where one call
    # on one machine reads them within 1-3 %: its median stands per layer
    # beside the tail, as query_mid_p50_ms
    big.append("histo8k.services")
    (mid,) = [m for m in BENCHMARK["per_layer"]
              if m["name"] == "query_mid_p50_ms"]
    (p50,) = [m for m in BENCHMARK["end_to_end"]
              if m["name"] == "query_p50_ms"]
    assert mid["workloads"] == ["histo8k.services"]
    assert "histo8k.services" not in p50["workloads"]
    assert (mid["source"], mid["unit"], mid["moves"]) == \
        (tail["source"], p50["unit"], tail["moves"])
    assert sorted(tail["workloads"]) == sorted(big)
    assert sorted(p90["workloads"]) == sorted(set(CELLS) - set(big))
    assert tail["source"] == "host_clock" and tail["unit"] == p90["unit"]
    # the same arithmetic over ALL the window's latencies
    import stats
    lats = {"a": [0.1 * i for i in range(1, 8)], "b": [0.05, 2.0]}
    spec = harness.load_json(BENCH, "layers", "query_tail_p90_ms.json")
    got = harness.load_module("readers", spec["reader"]).read(
        spec["args"], {"by_template": lats})
    assert got == pytest.approx(
        1e3 * stats.percentile([x for ls in lats.values() for x in ls], 90))
    spec = harness.load_json(BENCH, "layers", "query_mid_p50_ms.json")
    got = harness.load_module("readers", spec["reader"]).read(
        spec["args"], {"by_template": lats})
    assert got == pytest.approx(
        1e3 * stats.percentile([x for ls in lats.values() for x in ls], 50))


def test_query_work_counts_what_a_mask_over_every_sample_counts():
    """The samples a query needs moved, by two binary searches a row in
    the bulk, against the plain mask over the bulk and the tails, on a
    jittered data set with ticks taken."""
    import numpy as np
    import reference
    cfg = harness.load_json(BENCH, "configs", "dash8k.json")
    cfg.update(series=64, instances=8, jobs=4, range_h=1)
    data = harness.Dataset(cfg, 3_700_000_061, 1_790_000_000_000)
    for _ in range(5):
        data.advance()
    for q, n_tails, shift in (
            ("sum by (instance)(rate(http_requests_total[5m]))", 5, 5),
            ('rate(http_requests_total{job="job-1"}[5m])', 2, 0),
            ('max_over_time(http_requests_total{job="job-3"}[5m])', 0, -7)):
        start = data.start + shift * data.step
        asked = dict(query=q, start=start, end=start + 20 * data.step,
                     n_tails=n_tails)
        idx = reference.select(data.labels,
                               *reference.selector(reference.parse(q)))
        lo, hi = asked["start"] - 300_000, asked["end"]
        want = sum(int(((ts[idx] > lo) & (ts[idx] <= hi)).sum())
                   for ts in [data.ts] + [t for t, _ in data.tails[:n_tails]])
        assert want > 0
        assert harness.query_work(data, asked)["samples"] == want
