"""The seam's CPU shares and background overlaps (`fetch_cpu_pct`,
`tile_build_cpu_pct`, `{flush,merge,convert,gc}_overlap_pct`), read by
`counter_ratio` on a pair of /metrics snapshots that a served run on the
CPU recorded (`metrics_pair.json`, beside this file): 128 series, twelve
refreshes under live ingest, a spill to the pool and a forced merge
during them, recorded from a tree with the series and from one without."""

import json
import os

import pytest

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "tests", "metrics_pair.json")) as f:
    PAIR = json.load(f)
STAGES = ("index_search", "collect", "decode", "assemble", "assemble_native",
          "rollup")
NAMES = ("fetch_cpu_pct", "tile_build_cpu_pct", "flush_overlap_pct",
         "merge_overlap_pct", "convert_overlap_pct", "gc_overlap_pct")


def _by_hand(name: str, ctx: dict) -> float:
    """What a share reads, spelled out over the recorded deltas."""
    def d(series):
        return ctx["m1"][series] - ctx["m0"][series]
    if name == "fetch_cpu_pct":
        cpu = sum(d(f'vm_fetch_phase_cpu_seconds_total{{phase="{s}"}}')
                  for s in STAGES)
        wall = sum(d(f'vm_fetch_phase_seconds_total{{phase="{s}"}}')
                   for s in STAGES)
        return 100 * cpu / wall
    if name == "tile_build_cpu_pct":
        return 100 * d('vm_query_phase_cpu_seconds_total'
                       '{phase="device:tile_build"}') / \
            d('vm_query_phase_seconds_total{phase="device:tile_build"}')
    kind = name[:-len("_overlap_pct")]
    return 100 * d(f'vm_query_background_overlap_seconds_total'
                   f'{{kind="{kind}"}}') / d("vm_query_wall_seconds_total")


@pytest.mark.parametrize("name", NAMES)
def test_a_share_reads_a_recorded_window(name):
    """A share in (0, 100] that is the arithmetic done by hand; on the
    snapshots of a tree without the series, nothing (the metric is left
    out of the line)."""
    spec = harness.load_json(BENCH, "layers", name + ".json")
    assert spec["reader"] == "counter_ratio"
    reader = harness.load_module("readers", "counter_ratio")
    got = reader.read(spec["args"], PAIR["change"])
    assert 0.0 < got <= 100.0
    assert got == pytest.approx(_by_hand(name, PAIR["change"]), rel=1e-12)
    assert reader.read(spec["args"], PAIR["parent"]) is None


def test_the_shares_are_declared_where_they_read():
    """Each share moves `queries_per_s`; the two CPU shares stand only
    in the HA pair, whose fetch and tile build last seconds a call (a
    thread clock that ticks at 10 ms charges ms-scale stages wrongly),
    the flush, merge and conversion overlaps where
    `pending_convert_ms_per_query` does (the cells that ingest), gc's in
    every cell."""
    bench = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    ingesting = by_name["pending_convert_ms_per_query"]["workloads"]
    want = {"fetch_cpu_pct": ["dash8k-ha2.refresh"],
            "tile_build_cpu_pct": ["dash8k-ha2.refresh"],
            "gc_overlap_pct": cells,
            "flush_overlap_pct": ingesting, "merge_overlap_pct": ingesting,
            "convert_overlap_pct": ingesting}
    for name in NAMES:
        m = by_name[name]
        assert m["workloads"] == want[name], name
        assert (m["unit"], m["source"], m["moves"]) == \
            ("%", "program_counter", "queries_per_s")
        assert m["better"] == ("higher" if "cpu" in name else "lower")
