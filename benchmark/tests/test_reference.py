"""The plain reference on samples small enough to work out by hand, and
the control's precision."""

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
    with pytest.raises(ValueError):
        reference.parse("avg(rate(m[5m]))")


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
