"""The reduction from a profiler trace to numbers, on a small recorded
trace (trace_small.json: a TPU v5 lite, the first 1.5 s of a traced
dash8k.explore window), and the readers' arithmetic."""

import json
import os

import pytest

import harness
import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
ROLLUPS = "^jit_(rollup_aggregate_tile|rollup_tile|topk_select_tile)$"


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return json.load(f)


def reader(name):
    return harness.load_module("readers", name)


def test_busy_is_the_union_not_the_sum(trace):
    ops = xtrace.device_lines(trace, xtrace.OPS_LINE)[0]
    # brute force: sweep the sorted edges, count the covered nanoseconds
    edges = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops])
    covered, depth, last = 0, 0, None
    for at, step in edges:
        if depth > 0:
            covered += at - last
        depth, last = depth + step, at
    assert xtrace.busy_s(trace) == pytest.approx(covered / 1e9, rel=1e-12)
    assert xtrace.busy_s(trace) == pytest.approx(0.009037799)
    # while loops hold their children: the plain sum counts them twice
    assert sum(d for _, _, d in ops) / 1e9 > 1.9 * xtrace.busy_s(trace)
    # ... and the ops fill the programs they belong to
    launched = sum(e[2] for e in xtrace.programs(trace, "."))
    assert xtrace.busy_s(trace) == pytest.approx(launched / 1e9, rel=0.01)


def test_union_merges_nested_and_touching():
    assert xtrace.union([["a", 0, 10], ["b", 2, 3], ["c", 10, 5],
                         ["d", 20, 1]]) == [[0, 15], [20, 21]]


def test_no_device_op_reads_as_nothing_not_zero():
    host_only = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench:query_range", 0, 5]]}]}]}
    assert xtrace.busy_s(host_only) is None
    ctx = dict(trace=host_only, window_s=1.0, queries=3, peaks={}, work=list)
    assert reader("trace_busy").read({}, ctx) is None
    assert reader("trace_program_ms").read({"pattern": "."}, ctx) is None
    assert reader("roofline").read({"pattern": "."}, ctx) is None
    assert reader("trace_busy").read({}, dict(ctx, trace=None)) is None


def test_programs_match_by_name_without_fingerprint(trace):
    got = xtrace.programs(trace, ROLLUPS)
    assert [e[0].split("(")[0] for e in got] == \
        ["jit_rollup_tile", "jit_rollup_tile", "jit_topk_select_tile"]
    assert sum(e[2] for e in got) == 2385759 + 2384467 + 4160213
    assert len(xtrace.programs(trace, "^jit_rollup_tile$")) == 2
    assert not xtrace.programs(trace, "^rollup_tile$")


def test_readers_on_the_recorded_trace(trace):
    work = [dict(samples=694_080, out_values=482 * 361)] * 3
    ctx = dict(trace=trace, window_s=1.5, queries=3, work=lambda: work,
               peaks={"hbm_bytes_per_s": 819e9})
    assert reader("trace_busy").read({}, ctx) == \
        pytest.approx(100 * (1 - 0.009037799 / 1.5))
    assert reader("trace_program_ms").read({"pattern": ROLLUPS}, ctx) == \
        pytest.approx(8.930439 / 3)
    least_s = 3 * (694_080 * 8 + 482 * 361 * 4) / 819e9
    assert reader("roofline").read({"pattern": ROLLUPS}, ctx) == \
        pytest.approx(100 * least_s / 0.008930439)
    assert reader("roofline").least_bytes(10, 5) == 100


def test_top_ops_and_idle_gaps(trace):
    top = xtrace.top_ops(trace, 3)
    assert [n for n, _ in top] == ["while.1", "while.23", "fusion.7"]
    assert top[0][1] == pytest.approx(0.004728944)
    gaps = dict(xtrace.idle_gaps(trace))
    # a gap goes to the INNERMOST mark covering it: the server's phases
    # inside the client's call; what stays with bench:query_range is the
    # client's own (0.8148 s before the vm: marks were read)
    assert gaps["vm:serve:rows"] == pytest.approx(0.3)
    assert gaps["vm:fetch:wait"] == pytest.approx(0.038)
    assert gaps["vm:serve:send"] == pytest.approx(0.004)
    assert gaps["bench:query_range"] == pytest.approx(0.028924822)
    assert gaps["unmarked"] == pytest.approx(0.314477469)
    assert sum(v for k, v in gaps.items() if k != "unmarked") == \
        pytest.approx(0.814770949)
    # the shares add up to the device's idle time between its ops
    busy = xtrace.union(xtrace.device_lines(trace, xtrace.OPS_LINE)[0])
    assert sum(gaps.values()) == pytest.approx(
        (busy[-1][0] - busy[0][1] - sum(e - s for s, e in busy[1:-1])) / 1e9)
    assert xtrace.op_name("%fusion.2 = s32[8]{0} fusion(...)") == "fusion.2"


def test_counter_ratio():
    read = reader("counter_ratio").read
    ctx = dict(queries=4,
               m0={'f{p="a"}': 1.0, "hits": 2.0, "s_sum": 1.0, "s_count": 10.0},
               m1={'f{p="a"}': 2.0, 'f{p="b"}': 0.5, "hits": 6.0,
                   "s_sum": 3.0, "s_count": 20.0})
    assert read({"num": ["hits"], "den": "queries", "scale": 100}, ctx) == 100.0
    assert read({"num": ["f{"], "den": "queries", "scale": 1000}, ctx) == 375.0
    assert read({"num": ["s_sum"], "den": ["s_count"], "scale": 1000}, ctx) == 200.0
    assert read({"num": ["hits"]}, ctx) == 4.0
    assert read({"num": ["absent_total"], "den": "queries"}, ctx) is None
    assert read({"num": ["hits"], "den": ["absent"]}, ctx) is None


def host_marks(trace):
    return [e for p in trace["planes"] if not p["name"].startswith("/device")
            for line in p["lines"] for e in line["events"]]


def test_idle_gaps_equal_a_brute_force_over_every_edge(trace):
    """Between two neighbouring edges (of a mark or of a gap) nothing
    changes: charge each such stretch to the covering mark that started
    last, and the sums are idle_gaps'."""
    marks = host_marks(trace)
    assert {m[0].split(":")[0] for m in marks} == {"bench", "vm"}
    busy = xtrace.union(xtrace.device_lines(trace, xtrace.OPS_LINE)[0])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    edges = sorted({t for m in marks for t in (m[1], m[1] + m[2])} |
                   {t for g in gaps for t in g})
    want = {}
    for t0, t1 in zip(edges, edges[1:]):
        if not any(g0 <= t0 and t1 <= g1 for g0, g1 in gaps):
            continue
        cover = [m for m in marks if m[1] <= t0 and t1 <= m[1] + m[2]]
        name = max(cover, key=lambda m: (m[1], -m[2]))[0] if cover \
            else "unmarked"
        want[name] = want.get(name, 0) + (t1 - t0) / 1e9
    got = dict(xtrace.idle_gaps(trace, 99))
    assert got.keys() == want.keys()
    for name in want:
        assert got[name] == pytest.approx(want[name], abs=1e-12)


def test_innermost_is_the_mark_that_started_last():
    spans = xtrace.innermost([["a", 0, 100], ["b", 10, 30], ["c", 20, 10],
                              ["d", 60, 50], ["e", 200, 5]])
    assert spans == [[0, 10, "a"], [10, 20, "b"], [20, 30, "c"],
                     [30, 40, "b"], [40, 60, "a"], [60, 110, "d"],
                     [200, 205, "e"]]
    # two that start together: the shorter is inside the longer
    assert xtrace.innermost([["out", 0, 10], ["in", 0, 4]]) == \
        [[0, 4, "in"], [4, 10, "out"]]
    assert xtrace.innermost([]) == []
