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
