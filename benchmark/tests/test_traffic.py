"""The ticker's query lists and rounds, the per-shape reader, and the
generator `intervals`: what it draws from a seed and where."""

import re

import harness
import reference

BENCH = harness.HERE
CFG = {"metric": "m", "jobs": 3,
       "queries": {"panel": {"templates": ["sum by (instance)(rate(m[5m]))"]}}}


def ticker():
    return harness.load_module("traffic", "ticker")


def test_a_mix_carries_its_own_queries_and_sizes_them_from_the_config():
    mix = harness.load_json(BENCH, "traffic", "explore.json")
    lists = ticker().expand(CFG, mix["queries"])
    assert len(lists) == 4
    for tmpl, texts in lists:
        assert "{metric}" in tmpl and len(texts) == 3
        assert all('m{job="job-' in t and "{" not in t.replace('{job=', "")
                   for t in texts)


def test_a_mix_may_name_the_configurations_own_list():
    assert ticker().expand(CFG, "panel") == [
        ("sum by (instance)(rate(m[5m]))", ["sum by (instance)(rate(m[5m]))"])]


def test_every_seed_asks_the_templates_in_equal_shares():
    mix = harness.load_json(BENCH, "traffic", "explore.json")
    for seed in (1, 2_400_000_011):
        gen = ticker().Generator(None, None, CFG, mix, seed)
        asked = [gen._next_query() for _ in range(24)]
        for r in range(0, 24, 4):
            assert sorted(i for i, _ in asked[r:r + 4]) == [0, 1, 2, 3]
        assert len({q for _, q in asked[:12]}) == 12


def test_client_percentile_reads_one_shape_or_nothing():
    reader = harness.load_module("readers", "client_percentile")
    ctx = {"by_template": {"rate({metric}[5m])": [0.1, 0.3, 0.2],
                           "topk(10, rate({metric}[5m]))": [0.05]}}
    assert reader.read({"pattern": r"^rate\(", "percentile": 50}, ctx) == 200.0
    assert reader.read({"pattern": r"^topk\(", "percentile": 50}, ctx) == 50.0
    assert reader.read({"pattern": "^max_over_time", "percentile": 50},
                       ctx) is None


def test_the_window_line_names_three_slowest_and_the_slides():
    import run
    win = dict(latencies=[0.03, 0.4, 0.02, 2.5, 0.05, 0.6],
               producer_wait_s=0.0123)
    m0 = {run.SLIDES: 1.0}
    line = run.window_line(win, 6, 2757, m0, {run.SLIDES: 3.0})
    assert "6 ticks of ingest of the 2757 the anchor allowed" in line
    assert "producer_wait_s 0.0123" in line
    assert "2500.0 ms at query 3, 600.0 ms at query 5, 400.0 ms at query 1" \
        in line
    assert line.endswith("slides of the resident window 2")
    # a program that does not export the counter says so, not 0
    assert run.window_line(win, 6, 2757, {}, {}).endswith("not exported")


# ---- the generator `intervals` and the mixes PR 37 added

H = 3_600_000
TSBS = dict(harness.load_json(BENCH, "configs", "tsbs-devops-512.json"),
            hosts=16, range_h=26)


def intervals(seed, data=None):
    data = data or harness.Dataset(TSBS, 5, 1_790_000_000_000)
    mix = harness.load_json(BENCH, "traffic", "devops.json")
    return harness.load_module("traffic", "intervals").Generator(
        None, data, TSBS, mix, seed), data, mix


def test_intervals_draws_the_same_queries_for_a_seed():
    (a, data, _), (b, _, _) = intervals(3_700_000_041), intervals(3_700_000_041)
    c, _, _ = intervals(3_700_000_042)
    qa, qb, qc = ([g._next_query() for _ in range(40)] for g in (a, b, c))
    assert qa == qb and qa != qc
    # every seed asks the same ranges in equal shares, in another order
    shape = lambda qs: sorted((q["template"], q["start"]) for q in qs[:26])
    assert shape(qa)[:13] == shape(qc)[:13]
    assert all(r["n_tails"] == 0 for r in qa)


def test_every_start_is_a_multiple_of_its_step_inside_the_bulk():
    gen, data, mix = intervals(3_700_000_043)
    first, newest = int(data.ts.min()), int(data.ts.max())
    starts = {0: set(), 1: set()}
    for _ in range(200):
        r = gen._next_query()
        tmpl = mix["templates"][r["template"]]
        assert r["step"] == tmpl["step_s"] * 1000 == H
        assert r["end"] - r["start"] == tmpl["range_s"] * 1000
        assert r["start"] % r["step"] == 0
        # (start - window, end] holds only times the bulk covers
        assert r["start"] - tmpl["window_s"] * 1000 >= first - data.scrape
        assert r["end"] <= newest
        starts[r["template"]].add(r["start"])
    # 26 h of data: 13 aligned starts of a 12 h range, 17 of an 8 h one,
    # walked whole before one comes again
    assert len(starts[0]) == 13 and len(starts[1]) == 17
    fresh, _, _ = intervals(3_700_000_043, data)
    one = [fresh._next_start(0) for _ in range(26)]
    assert sorted(one[:13]) == sorted(one[13:]) == sorted(starts[0])
    # rounds: one of each template, in either order
    pairs = [gen._next_query()["template"] for _ in range(40)]
    assert all(sorted(pairs[i:i + 2]) == [0, 1] for i in range(0, 40, 2))


def test_a_query_names_eight_distinct_hosts_drawn_anew():
    gen, data, _ = intervals(3_700_000_044)
    seen = set()
    for _ in range(60):
        r = gen._next_query()
        assert "{hosts" not in r["query"]
        if r["template"] == 1:
            hosts = reference.parse(r["query"])[2][3]["hostname"][1].split("|")
            assert len(hosts) == len(set(hosts)) == 8
            assert set(hosts) <= {f"host_{i}" for i in range(16)}
            seen.add(tuple(hosts))
        else:
            assert not re.search("hostname=~", r["query"])
    assert len(seen) == 30


def test_intervals_warm_up_asks_every_range_or_a_few_draws():
    asked = []

    class Recorder:
        def query_range(self, q, start, end, step, nocache):
            asked.append((q, start, end, step, nocache))
            return b'{"status":"success","isPartial":false}'
    gen, data, mix = intervals(3_700_000_045)
    gen.server = Recorder()
    assert gen.warm_up() == 13 + 8 == len(asked)
    plain = mix["templates"][0]["query"]
    assert sorted(s for q, s, *_ in asked if q == plain) == \
        sorted(gen.starts[0])
    assert all(nocache and step == H for *_, step, nocache in asked)
    win = gen.window(0.05)
    assert win["producer_wait_s"] == 0.0 and win["failed"] == 0
    assert len(win["asked"]) == len(win["latencies"]) >= 2
    assert win["kept"][-1]["query"] == win["asked"][-1]["query"] or \
        any(k["query"] == win["asked"][-1]["query"] for k in win["kept"])
    assert set(gen.by_template(win)) <= {t["query"] for t in mix["templates"]}
    gen.close()


def test_intervals_refuses_a_mix_that_ingests_and_a_bulk_too_short():
    import pytest
    data = harness.Dataset(dict(TSBS, range_h=9), 5, 1_790_000_000_000)
    mix = harness.load_json(BENCH, "traffic", "devops.json")
    mod = harness.load_module("traffic", "intervals")
    with pytest.raises(ValueError, match="holds no range"):
        mod.Generator(None, data, TSBS, mix, 1)
    with pytest.raises(ValueError, match="does not ingest"):
        mod.Generator(None, data, TSBS, dict(mix, ingest=True), 1)


def test_explore_live_is_explore_under_ingest_without_topk():
    explore = harness.load_json(BENCH, "traffic", "explore.json")
    live = harness.load_json(BENCH, "traffic", "explore_live.json")
    assert set(live) == set(explore) and "preroll_steps" not in live
    differs = {k for k in live if live[k] != explore[k]}
    assert differs == {"what", "ingest", "queries"}
    assert live["ingest"] is True and explore["ingest"] is False
    assert live["queries"]["params"] == explore["queries"]["params"]
    assert live["queries"]["templates"] == [
        t for t in explore["queries"]["templates"] if not t.startswith("topk")]
