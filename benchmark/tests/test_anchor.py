"""The anchor: where the bulk lies behind the wall clock, the ceiling no
window may pass, and the one calendar month that holds all of it."""

import calendar
import time

import numpy as np
import pytest

import harness

BENCH = harness.HERE
H = 3_600_000
MIX = harness.load_json(BENCH, "traffic", "refresh.json")
WARM_TICKS = harness.load_module("traffic", "ticker").WARM_TICKS


def at(*ymdhm) -> int:
    return calendar.timegm(ymdhm + (0,)) * 1000


def month(ms: int) -> tuple:
    return time.gmtime(ms // 1000)[:2]


def small(config: str) -> dict:
    cfg = harness.load_json(BENCH, "configs", config + ".json")
    if cfg["deployment"] == "histogram":
        cfg.update(series=96, instances=8, jobs=4)
    else:
        cfg.update(series=64, instances=8, jobs=4)
    return cfg


# the month's 1st (where the bulk would straddle its end), 2nd (where the
# 48 h still reach back over it) and 15th day; the first minutes of a
# month, when `latest` itself lies in the month before; a leap February
NOWS = [at(2026, 11, 1, 3, 0), at(2026, 11, 2, 14, 30), at(2026, 11, 15, 9, 0),
        at(2026, 11, 1, 0, 5), at(2028, 3, 1, 12, 0), at(2027, 1, 2, 23, 59)]


@pytest.mark.parametrize("now", NOWS)
@pytest.mark.parametrize("config", ["dash8k", "histo8k"])
def test_room_margin_and_one_month(config, now):
    cfg = small(config)
    data = harness.Dataset(cfg, 3_500_000_011, now)
    # room for the pre-roll, the warm-up and 2500 ticks of one step
    assert data.room() >= MIX["preroll_steps"] + WARM_TICKS + 2500
    assert harness.WINDOW_TICKS >= 2500
    assert data.latest <= now - harness.WALL_MARGIN_MS
    jitter = int(cfg["jitter_s"] * 1000)
    # every sample a run can make, from the bulk's first to the last
    # tick's under the ceiling, lies in one calendar month (UTC)
    assert data.ts.min() >= data.t_start - jitter
    assert month(data.t_start - jitter) == month(data.ts.min()) == \
        month(data.latest + jitter)
    # ... and nowhere near the retention's 13 months
    assert now - data.ts.min() < 35 * 24 * H
    # the first window ends beyond every bulk sample
    assert data.ts.max() < data.end <= data.ts.max() + data.step + jitter


def test_the_bulk_is_two_days_back_at_a_60_s_step():
    now = at(2026, 11, 15, 9, 0)
    newest, latest = harness.anchor(now, 60_000, 6 * H)
    assert latest == now - harness.WALL_MARGIN_MS
    assert now - newest == 48 * H


def test_a_months_end_moves_the_whole_span_back():
    step, reach = 60_000, 6 * H + 62_000
    first = at(2026, 11, 1, 0, 0)
    for hours in range(0, 24 * 62):
        now = at(2026, 10, 20, 0, 0) + hours * H + 7 * 60_000
        newest, latest = harness.anchor(now, step, reach)
        assert latest - newest == (harness.WINDOW_TICKS +
                                   harness.SETUP_STEPS) * step
        assert latest <= now - harness.WALL_MARGIN_MS
        assert month(newest - reach) == month(latest + step - 1), hours
        if latest != now - harness.WALL_MARGIN_MS:
            # moved back: the ceiling is the month's end less a step
            assert latest == first - step or latest == \
                at(2026, 12, 1, 0, 0) - step
    # the days on which the span is moved: from the month's first minutes
    # until the bulk's first sample has left the month before
    newest, latest = harness.anchor(first + 54 * H, step, reach)
    assert latest == first - step
    newest, latest = harness.anchor(first + 55 * H, step, reach)
    assert latest == first + 55 * H - harness.WALL_MARGIN_MS


def test_advance_and_take_raise_at_the_ceiling():
    data = harness.Dataset(small("dash8k"), 7, at(2026, 11, 15, 9, 0))
    room = data.room()
    data.advance(room - 1)
    tail = data.advance()
    assert data.room() == 0 and data.end <= data.latest
    with pytest.raises(RuntimeError, match="ceiling"):
        data.advance()
    with pytest.raises(RuntimeError, match="ceiling"):
        data.take(tail)
    assert len(data.tails) == 2 and data.room() == 0


def test_a_seed_gives_the_same_values_at_any_anchor():
    """The generator draws after the anchor is set and its draws do not
    depend on it: the same seed gives the same values and the same
    jitter, at other timestamps."""
    cfg = small("dash8k")
    a = harness.Dataset(cfg, 11, at(2026, 11, 15, 9, 0))
    b = harness.Dataset(cfg, 11, at(2026, 11, 1, 3, 0))
    assert a.t_start != b.t_start
    np.testing.assert_array_equal(a.vals, b.vals)
    np.testing.assert_array_equal(a.ts - a.t_start, b.ts - b.t_start)
    ta, tb = a.advance(), b.advance()
    np.testing.assert_array_equal(ta[1], tb[1])


# ---- PR 37: a configuration that never ingests, and the four that do

def test_a_configuration_that_never_ingests_keeps_no_room():
    step, reach = 3_600_000, 27 * H
    now = at(2026, 11, 15, 9, 0)
    newest, latest = harness.anchor(now, step, reach, ingests=False)
    assert newest == latest == now - harness.WALL_MARGIN_MS
    # with the ticks' room an hour's step does not fit a month
    with pytest.raises(ValueError, match="calendar month"):
        harness.anchor(now, step, reach)
    # a month's end still moves the whole span back, never forward
    for hours in range(0, 24 * 40):
        now = at(2026, 10, 20, 0, 0) + hours * H + 7 * 60_000
        newest, latest = harness.anchor(now, step, reach, ingests=False)
        assert newest == latest <= now - harness.WALL_MARGIN_MS
        assert month(newest - reach) == month(latest)


# now -> `latest` as PR 35's anchor gave it: ten minutes back, or a
# month's end less a step; `newest` 2870 steps under it
PARENT_ANCHORS = {
    at(2026, 11, 15, 9, 0): at(2026, 11, 15, 8, 50),
    at(2026, 11, 1, 3, 0): at(2026, 10, 31, 23, 59),
    at(2027, 1, 2, 23, 59): at(2026, 12, 31, 23, 59),
}


@pytest.mark.parametrize("config", ["dash32k", "dash8k", "histo8k",
                                    "dash32k-4chip"])
@pytest.mark.parametrize("now", sorted(PARENT_ANCHORS))
def test_the_four_ingesting_configurations_are_anchored_as_before(config,
                                                                  now):
    cfg = harness.load_json(BENCH, "configs", config + ".json")
    assert "ingests" not in cfg
    n_samples = int(cfg["range_h"] * H) // int(cfg["scrape_interval_s"] * 1000)
    span = (n_samples - 1) * int(cfg["scrape_interval_s"] * 1000)
    reach = span + 60_000 + int(cfg["jitter_s"] * 1000)
    room = (harness.WINDOW_TICKS + harness.SETUP_STEPS) * 60_000
    newest, latest = harness.anchor(now, 60_000, reach)
    assert (newest, latest) == harness.anchor(now, 60_000, reach, True)
    assert latest == PARENT_ANCHORS[now] and newest == latest - room
    small_cfg = small(config if config != "dash32k-4chip" else "dash32k")
    data = harness.Dataset(small_cfg, 11, now)
    assert data.latest == latest
    assert data.t_start == (newest - span) // 60_000 * 60_000
