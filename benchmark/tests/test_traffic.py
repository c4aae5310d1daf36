"""The ticker's query lists and rounds, and the per-shape reader."""

import harness

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
