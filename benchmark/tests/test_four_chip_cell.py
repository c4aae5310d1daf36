"""What `dash32k.refresh4` adds to the instrument: the reader of an HLO
op's device time on a hand-made trace with two device planes, and the
four-chip configuration against the one-chip one it shares its samples
with."""

import pytest

import harness

BENCH = harness.HERE
COLLECTIVES = harness.load_json(
    BENCH, "layers", "collective_ms_per_query.json")["args"]

# two chips, 4 queries: each ran the step's all-reduce once a query (the
# async pair on chip 1), beside ops that are none
TWO_PLANES = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_sharded_rollup_aggregate(1)", 0, 9_000_000]]},
        {"name": "XLA Ops", "events": [
            ["%fusion.2 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %p)", 0, 5_000_000],
            ["%all-reduce.2 = (f32[4,4]{1,0}) all-reduce(%fusion.4)", 5_000_000, 300_000],
            ["%all-reduce.2 = (f32[4,4]{1,0}) all-reduce(%fusion.4)", 6_000_000, 500_000],
            ["%reduce.7 = f32[4]{0} reduce(f32[4,4]{1,0} %x)", 7_000_000, 100_000]]}]},
    {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [
            ["%all-reduce-start.1 = f32[4,4]{1,0} all-reduce-start(%f)", 0, 100_000],
            ["%all-reduce-done.1 = f32[4,4]{1,0} all-reduce-done(%s)", 200_000, 700_000],
            ["%all-gather.3 = s32[16]{0} all-gather(s32[4]{0} %c)", 900_000, 400_000]]}]},
    {"name": "/device:TPU:2", "lines": []},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench:query_range", 0, 9_000_000]]}]},
]}


def reader():
    return harness.load_module("readers", "trace_op_ms")


def ctx(trace, queries=4):
    return dict(trace=trace, queries=queries, window_s=1.0)


def test_matching_ops_are_averaged_over_the_planes_that_ran_ops():
    # chip 0: 0.3 + 0.5 ms; chip 1: 0.1 + 0.7 + 0.4 ms; the plane without
    # ops is no chip of this run; 2.0 ms over 2 chips and 4 queries
    assert reader().read(COLLECTIVES, ctx(TWO_PLANES)) == \
        pytest.approx(2.0 / 2 / 4)
    # the op's own name is matched, not its operands (%fusion.4 feeds an
    # all-reduce and is none) nor a name that merely contains "reduce"
    assert reader().read({"pattern": "^fusion"}, ctx(TWO_PLANES)) == \
        pytest.approx(5.0 / 2 / 4)
    assert reader().read({"pattern": "^all-gather"}, ctx(TWO_PLANES)) == \
        pytest.approx(0.4 / 2 / 4)


def test_nothing_to_read_is_nothing_not_zero():
    assert reader().read({"pattern": "all-to-all"}, ctx(TWO_PLANES)) is None
    assert reader().read(COLLECTIVES, ctx(None)) is None
    assert reader().read(COLLECTIVES, ctx(TWO_PLANES, queries=0)) is None
    host_only = {"planes": TWO_PLANES["planes"][3:]}
    assert reader().read(COLLECTIVES, ctx(host_only)) is None


def test_one_chip_runs_no_collective():
    import json
    import os
    with open(os.path.join(BENCH, "tests", "trace_small.json")) as f:
        recorded = json.load(f)
    assert reader().read(COLLECTIVES, ctx(recorded, 3)) is None
    assert reader().read({"pattern": "^fusion"}, ctx(recorded, 3)) > 0


# every key of a configuration's file that the harness, the generator or
# the comparison reads: the two files must describe the same samples
DATA_KEYS = ("deployment", "metric", "series", "instances", "jobs",
             "range_h", "scrape_interval_s", "jitter_s", "max_increment",
             "query_step_s", "window_s", "queries", "limits", "assumed")


def test_the_four_chip_configuration_holds_dash32ks_samples_and_limits():
    one = harness.load_json(BENCH, "configs", "dash32k.json")
    four = harness.load_json(BENCH, "configs", "dash32k-4chip.json")
    for key in DATA_KEYS:
        assert four[key] == one[key], key
    # nothing else of the file reaches the data: what differs is prose,
    # the layout and the cut in chips
    assert set(four) - set(one) == {"layout", "chips"}
    assert set(k for k in one if four[k] != one[k]) == \
        {"name", "source", "source_sizes", "reduced", "kept", "guarantees"}
    # no guarantee is weakened: dash32k's three, to the word, and one more
    assert four["guarantees"][:3] == one["guarantees"]
    assert len(four["guarantees"]) == 4
    for key, why in one["reduced"].items():
        assert four["reduced"][key] == why
    assert set(four["reduced"]) - set(one["reduced"]) == {"chips"}
    lay = four["layout"]
    assert lay["chips"] == four["chips"] == 4
    assert lay["mesh"] == {"series": 4}
    assert lay["rows_per_chip"] * lay["chips"] == four["series"]


def test_the_cell_is_the_refresh_mix_on_four_chips():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"]
               if w["name"] == "dash32k.refresh4"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("dash32k-4chip", "refresh", 4)
    (cfg,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    four = harness.load_json(harness.ROOT, cfg["file"])
    assert cfg["source"] == four["source"] and len(cfg["source"]) <= 200
    assert cfg["reduced"] == list(four["reduced"])
    # it is the only cell that asks for more than one chip
    assert [w["name"] for w in bench["workloads"] if w["chips"] != 1] == \
        ["dash32k.refresh4"]
