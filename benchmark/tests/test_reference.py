"""The plain reference on samples small enough to work out by hand, the
forms TSBS asks against a brute-force double loop, every query text the
cells ask held to its tuples, and the control's precision."""

import glob
import json
import math
import os
import re

import numpy as np
import pytest

import compare
import reference

MIN = 60_000


def _series():
    """Two counters scraped each 15 s for 10 minutes; b has a reset."""
    ts = np.arange(1, 41, dtype=np.int64)[None, :] * 15_000
    ts = np.vstack([ts, ts])
    a = np.arange(40, dtype=np.float64) * 30          # 2 a second
    b = np.arange(40, dtype=np.float64) * 15          # 1 a second ...
    b[20:] -= b[20]                                   # ... reset at i=20
    labels = [{"__name__": "m", "instance": "x", "job": "j", "idx": "0"},
              {"__name__": "m", "instance": "x", "job": "k", "idx": "1"}]
    return labels, ts, np.vstack([a, b])


@pytest.mark.parametrize("q,shape", [
    ('sum by (instance)(rate(m{job="j"}[5m]))', "sum"),
    ('rate(m[5m])', "rollup"),
    ('max_over_time(m{job="j",idx="0"}[5m])', "rollup"),
    ('topk(10, rate(m[5m]))', "topk"),
    ('histogram_quantile(0.99, sum by (le)(rate(b[5m])))', "hq"),
])
def test_parse_knows_the_five_shapes(q, shape):
    assert reference.parse(q)[0] == shape


def test_parse_refuses_what_it_does_not_know():
    for q in ("min(rate(m[5m]))", "rate(m[5m]) > 1", "rate(m[5m]) + 1",
              'rate(m{job!="j"}[5m])', 'rate(m{job!~"j"}[5m])', "m",
              "min_over_time(m[5m])"):
        with pytest.raises(ValueError):
            reference.parse(q)


def test_rate_uses_the_sample_before_the_window_and_removes_resets():
    labels, ts, vals = _series()
    grid = np.array([6 * MIN, 10 * MIN], dtype=np.int64)
    kind, out_labels, out = reference.evaluate(
        reference.parse("rate(m[5m])"), labels, ts, vals, grid)
    assert kind == "rows" and "__name__" not in out_labels[0]
    # a: 2/s everywhere; b: 1/s, less the one step the reset swallowed
    # (the counter restarts AT the previous value: 285 in 300 s)
    assert out[0] == pytest.approx([2.0, 2.0])
    assert out[1] == pytest.approx([0.95, 0.95])


def test_rate_is_nan_where_the_window_is_empty():
    labels, ts, vals = _series()
    grid = np.array([20 * MIN], dtype=np.int64)
    _, _, out = reference.evaluate(reference.parse("rate(m[5m])"), labels,
                                   ts, vals, grid)
    assert np.isnan(out).all()


def test_max_over_time_keeps_the_name_and_sum_groups():
    labels, ts, vals = _series()
    grid = np.array([10 * MIN], dtype=np.int64)
    _, l, out = reference.evaluate(
        reference.parse("max_over_time(m[5m])"), labels, ts, vals, grid)
    assert l[0]["__name__"] == "m" and out[0, 0] == 39 * 30
    _, l, out = reference.evaluate(
        reference.parse("sum by (instance)(rate(m[5m]))"), labels, ts, vals,
        grid)
    assert l == [{"instance": "x"}] and out[0, 0] == pytest.approx(2.95)


def test_histogram_quantile_interpolates_inside_the_bucket():
    # 100 requests: 50 under 0.1, 90 under 0.5, all under 1
    les = np.array([0.1, 0.5, 1.0, np.inf])
    m = np.array([[50.0], [90.0], [100.0], [100.0]])
    assert reference._histogram_quantile(0.99, les, m)[0] == \
        pytest.approx(0.5 + 0.5 * 9 / 10)
    assert reference._histogram_quantile(0.25, les, m)[0] == \
        pytest.approx(0.05)
    assert np.isnan(reference._histogram_quantile(0.5, les, m * 0)[0])


def test_bfloat16_keeps_eight_bits():
    x = np.array([1.0, 1.0 + 2 ** -9, 3.14159, np.nan, -1000.123])
    y = reference.to_bfloat16(x)
    assert y[0] == 1.0 and np.isnan(y[3])
    assert np.all(np.abs(y[[1, 2, 4]] - x[[1, 2, 4]]) / np.abs(x[[1, 2, 4]])
                  <= 2 ** -8)
    assert y[2] != x[2]


def test_topk_comparison_lets_near_ties_fall_either_way():
    ref = np.array([[10.0], [9.0], [9.0 * (1 - 1e-7)], [1.0]])
    labels = [{"i": str(i)} for i in range(4)]
    key = [compare.labels_key(l) for l in labels]
    either = {key[0]: np.array([10.0]), key[2]: np.array([ref[2, 0]])}
    assert compare.compare("topk:2", either, labels, ref)["rel_err"] < 1e-6
    wrong = {key[0]: np.array([10.0]), key[3]: np.array([1.0])}
    assert compare.compare("topk:2", wrong, labels, ref)["rel_err"] > 0.5
    short = {key[0]: np.array([10.0])}
    assert compare.compare("topk:2", short, labels, ref)["nan_mismatch"] == 1


# ---- the forms TSBS asks: regex selectors, avg_over_time, max / avg by

HOSTS = ["host_1", "host_2", "host_11", "host_21", "host_12", "xhost_1"]
METRICS = ["cpu_usage_user", "cpu_usage_system", "cpu_usage_user_x"]


def _fleet(seed=7):
    """6 hosts x 3 metrics x 40 samples, one every 10 s from 10 s on; a
    gap of 8 samples in every host_11 series, host_21's series all end
    early, and a host name and a metric name that an unanchored regex
    would also match."""
    rng = np.random.default_rng(seed)
    labels = [{"__name__": m, "hostname": h, "region": "r" + str(i % 2)}
              for i, h in enumerate(HOSTS) for m in METRICS]
    ts = np.tile(np.arange(1, 41, dtype=np.int64) * 10_000, (len(labels), 1))
    vals = np.round(rng.uniform(0, 100, ts.shape), 2)
    return labels, ts, vals


def _window(ts_row, vals_row, t, w):
    return [v for s, v in zip(ts_row, vals_row) if t - w < s <= t]


def _brute(labels, ts, vals, grid, w, name_rx, host_rx, over, by, agg):
    """A double loop over series and grid steps: what the query means."""
    rows = {}
    for l, ts_row, vals_row in zip(labels, ts, vals):
        if not re.fullmatch(name_rx, l["__name__"]) or \
                not re.fullmatch(host_rx, l["hostname"]):
            continue
        out = []
        for t in grid:
            win = _window(ts_row, vals_row, t, w)
            if not win:
                out.append(math.nan)
            elif over == "avg_over_time":
                out.append(sum(win) / len(win))
            else:
                out.append(max(win))
        rows.setdefault(tuple((k, l[k]) for k in by), []).append(out)
    answer = {}
    for key, members in rows.items():
        row = []
        for j in range(len(grid)):
            have = [m[j] for m in members if not math.isnan(m[j])]
            if not have:
                row.append(math.nan)
            elif agg == "avg":
                row.append(sum(have) / len(have))
            else:
                row.append({"max": max, "sum": sum}[agg](have))
        answer[key] = row
    return answer


FORMS = [
    # (query, name regex, hostname regex, rollup, by, aggregate)
    ('avg(avg_over_time({__name__=~"cpu_(usage_user)"}[1m])) '
     'by (__name__, hostname)', "cpu_(usage_user)", ".*", "avg_over_time",
     ("__name__", "hostname"), "avg"),
    ('max(max_over_time({__name__=~"cpu_(usage_user|usage_system)",'
     'hostname=~"host_1|host_21"}[1m])) by (__name__)',
     "cpu_(usage_user|usage_system)", "host_1|host_21", "max_over_time",
     ("__name__",), "max"),
    ('max by (__name__)(max_over_time({__name__=~"cpu_(usage_user|'
     'usage_system)",hostname=~"host_1|host_21"}[1m]))',
     "cpu_(usage_user|usage_system)", "host_1|host_21", "max_over_time",
     ("__name__",), "max"),
    ('avg by (region)(avg_over_time(cpu_usage_user{hostname=~"host_.*"}'
     '[90s]))', "cpu_usage_user", "host_.*", "avg_over_time", ("region",),
     "avg"),
    ('sum(max_over_time({hostname=~"host_1.*"}[30s])) by (hostname)',
     ".*", "host_1.*", "max_over_time", ("hostname",), "sum"),
    ('avg(avg_over_time({__name__=~"cpu_.*"}[1m]))', "cpu_.*", ".*",
     "avg_over_time", (), "avg"),
]


@pytest.mark.parametrize("form", FORMS, ids=lambda f: f[0][:40])
def test_the_new_forms_equal_a_brute_force_double_loop(form):
    q, name_rx, host_rx, over, by, agg = form
    labels, ts, vals = _fleet()
    # holes: host_11 misses samples 10..17, host_21 ends after sample 20
    keep = np.ones(ts.shape, dtype=bool)
    for i, l in enumerate(labels):
        if l["hostname"] == "host_11":
            keep[i, 10:18] = False
        if l["hostname"] == "host_21":
            keep[i, 20:] = False
    # a hole is a sample that never was: move it out of every window
    ts = np.where(keep, ts, 10_000_000 + ts)
    order = np.argsort(ts, axis=1, kind="stable")
    ts = np.take_along_axis(ts, order, axis=1)
    vals = np.take_along_axis(vals, order, axis=1)
    ast = reference.parse(q)
    w = reference.window_of(ast)
    # the last steps lie beyond every sample: NaN for every member
    grid = np.arange(60_000, 510_001, 30_000, dtype=np.int64)
    kind, out_labels, out = reference.evaluate(ast, labels, ts, vals, grid)
    want = _brute(labels, ts, vals, grid, w, name_rx, host_rx, over, by, agg)
    assert kind == "rows"
    got = {tuple((k, l[k]) for k in by): row
           for l, row in zip(out_labels, out)}
    assert set(got) == set(want) and len(out_labels) == len(want)
    for key, row in want.items():
        np.testing.assert_allclose(got[key], row, rtol=1e-13, equal_nan=True)
    assert np.isnan(out).any() and not np.isnan(out).all()
    assert reference.row_labels(ast, labels) == out_labels


def test_a_regex_matches_the_whole_value_as_prometheus_anchors_it():
    labels, _, _ = _fleet()
    one = reference.select(labels, None, {
        "__name__": ("=~", "cpu_(usage_user)"),
        "hostname": ("=~", "host_1|host_2")})
    assert [(labels[i]["hostname"], labels[i]["__name__"]) for i in one] == \
        [("host_1", "cpu_usage_user"), ("host_2", "cpu_usage_user")]
    # unanchored, the same patterns would take host_11, host_21, host_12,
    # xhost_1 and cpu_usage_user_x too
    loose = [i for i, l in enumerate(labels)
             if re.search("host_1|host_2", l["hostname"])
             and re.search("cpu_(usage_user)", l["__name__"])]
    assert len(loose) == 12
    # a label the series lacks is the empty string
    assert len(reference.select(labels, None, {"rack": ("=~", ".*")})) == 18
    assert len(reference.select(labels, None, {"rack": ("=~", ".+")})) == 0
    assert len(reference.select(labels, "cpu_usage_user", {})) == 6


def test_avg_over_time_keeps_the_name_and_rate_drops_it():
    labels, ts, vals = _fleet()
    grid = np.array([120_000], dtype=np.int64)
    _, l, out = reference.evaluate(reference.parse(
        'avg_over_time({__name__=~"cpu_usage_user"}[1m])'), labels, ts, vals,
        grid)
    assert {x["__name__"] for x in l} == {"cpu_usage_user"} and len(l) == 6
    np.testing.assert_allclose(out[:, 0], vals[::3, 6:12].mean(axis=1))
    _, l, _ = reference.evaluate(reference.parse(
        'sum(rate({__name__=~"cpu_usage_user"}[1m])) by (__name__)'),
        labels, ts, vals, grid)
    assert l == [{}]        # rate dropped the name: one group, no label


def test_the_controls_rounding_reaches_the_new_rollup():
    labels, ts, vals = _fleet()
    grid = np.array([120_000, 240_000], dtype=np.int64)
    ast = reference.parse(FORMS[0][0])
    _, _, exact = reference.evaluate(ast, labels, ts, vals, grid)
    _, _, rounded = reference.evaluate(ast, labels, ts, vals, grid,
                                       reference.to_bfloat16)
    rel = np.abs(rounded - exact) / np.abs(exact)
    assert 1e-4 < rel.max() <= 2 ** -8


# ---- every text the cells ask, held to the tuples it parses to

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rollup(func, name, **matchers):
    return ("rollup", func, name, matchers, 300_000)


def _asts(metric: str, jobs: int):
    """{template: {text: tuples}} as PR 27-36's parser gave them."""
    hq = "histogram_quantile(0.99, sum by (%s)(rate(latency_bucket[5m])))"
    out = {
        "sum by (instance)(rate(http_requests_total[5m]))":
            ("sum", ("instance",), _rollup("rate", "http_requests_total")),
        hq % "le": ("hq", 0.99, ("sum", ("le",),
                                 _rollup("rate", "latency_bucket"))),
        hq % "le, job": ("hq", 0.99, ("sum", ("le", "job"),
                                      _rollup("rate", "latency_bucket"))),
    }
    for k in range(jobs):
        sel = '%s{job="job-%d"}[5m]' % (metric, k)
        rate = _rollup("rate", metric, job=f"job-{k}")
        out[f"sum by (instance)(rate({sel}))"] = ("sum", ("instance",), rate)
        out[f"rate({sel})"] = rate
        out[f"max_over_time({sel})"] = _rollup("max_over_time", metric,
                                               job=f"job-{k}")
        out[f"topk(10, rate({sel}))"] = ("topk", 10, rate)
    return out


def test_every_text_of_the_cells_parses_to_its_present_tuples():
    import harness
    bench = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    files = {c["name"]: c["file"] for c in bench["configs"]}
    expand = harness.load_module("traffic", "ticker").expand
    seen = 0
    for cell in bench["workloads"]:
        mix = harness.load_json(BENCH, "traffic", cell["traffic"] + ".json")
        if mix["generator"] != "ticker":
            continue
        cfg = harness.load_json(os.path.dirname(BENCH), files[cell["config"]])
        want = _asts(cfg["metric"], cfg["jobs"])
        for _, texts in expand(cfg, mix["queries"]):
            for text in texts:
                assert reference.parse(text) == want[text], text
                seen += 1
    # five refresh cells and services of one text, explore's 4 x 17,
    # explore_live's 3 x 17
    assert seen == 6 + 4 * 17 + 3 * 17
