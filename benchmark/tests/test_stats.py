"""The percentile, rate and spread arithmetic on fixed lists."""

import pytest

import stats


@pytest.mark.parametrize("pct,want", [(50, 5.5), (90, 9.1), (0, 1.0),
                                      (100, 10.0)])
def test_percentile_interpolates_between_ranks(pct, want):
    assert stats.percentile(list(range(10, 0, -1)), pct) == pytest.approx(want)


def test_percentile_of_one_value_and_of_none():
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_over_the_whole_window():
    assert stats.rate(90, 30.0) == 3.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_spread_is_quartile_distance_over_median():
    # statistics.quantiles(n=4) of 1..6: q1 1.75, q3 5.25, median 3.5
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)
