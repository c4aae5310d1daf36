"""The replica-pair data generator `counters_ha`: `dash8k`'s series read
by two scrapers, and the configuration `dash8k-ha2` beside `dash8k`."""

import calendar

import numpy as np

import harness

BENCH = harness.HERE
HA = harness.load_json(BENCH, "configs", "dash8k-ha2.json")
DASH = harness.load_json(BENCH, "configs", "dash8k.json")
SMALL = dict(series=64, instances=8, jobs=4, range_h=1)


def at(*ymdhm) -> int:
    return calendar.timegm(ymdhm + (0,)) * 1000


def small(cfg: dict) -> dict:
    return dict(cfg, **SMALL)


def gen(cfg: dict):
    return harness.load_module("deployments",
                               cfg["deployment"]).Deployment(cfg)


def test_the_ha_pair_is_dash8k_value_for_value():
    same = ["metric", "series", "instances", "jobs", "range_h",
            "scrape_interval_s", "jitter_s", "max_increment", "query_step_s",
            "window_s", "queries", "limits", "reduced", "source_sizes"]
    for key in same:
        assert HA[key] == DASH[key], key
    for key, text in DASH["assumed"].items():
        if key != "max_increment":
            assert HA["assumed"][key] == text
    assert HA["deployment"] == "counters_ha" and HA["replicas"] == 2
    assert HA["server_flags"] == ["-dedup.minScrapeInterval=15s"]
    assert HA["dedup_interval_s"] == HA["scrape_interval_s"] == 15
    assert len(HA["source"]) <= 200 and len(HA["guarantees"]) == 4
    assert gen(small(HA)).labels() == gen(small(DASH)).labels()
    assert len(gen(HA).labels()) == 8192


def test_rows_are_sorted_and_one_counter_only_grows():
    cfg = small(HA)
    g = gen(cfg)
    rng = np.random.default_rng(3_800_000_021)
    t_from, k = 1_794_000_000_000, 240
    ts, vals = g.scrapes(rng, t_from, k)
    assert ts.shape == vals.shape == (64, 2 * k)
    assert ts.dtype == np.int64 and vals.dtype == np.float64
    jitter = 2000
    # every sample of a call within the jitter of (t_from, t_from + k x 15 s]
    assert ts.min() >= t_from - jitter
    assert ts.max() <= t_from + k * 15_000 + jitter
    calls = [(ts, vals)]
    for _ in range(30):         # ticks: four scrapes by each replica
        t_from += k * 15_000
        k = 4
        calls.append(g.scrapes(rng, t_from, k))
        assert calls[-1][0].shape == (64, 8)
        assert calls[-1][0].min() >= t_from - jitter
        assert calls[-1][0].max() <= t_from + 60_000 + jitter
    all_ts = np.hstack([t for t, _ in calls])
    all_vals = np.hstack([v for _, v in calls])
    # sorted along the row across calls too: no tick's sample is older
    # than one handed out before it; equal timestamps may occur
    assert (np.diff(all_ts, axis=1) >= 0).all()
    assert (np.diff(all_vals, axis=1) >= 0).all()
    assert (all_vals == np.round(all_vals)).all()
    # 15 s add what dash8k's 15 s add: two draws from [0, 25)
    per_15s = all_vals[:, -1] / (all_ts[:, -1] - all_ts[:, 0]) * 15_000
    assert 22 < per_15s.mean() < 26
    # two samples a scrape interval: a dedup keeps about half
    kept = harness.reference.dedup(all_ts[0], all_vals[0], 15_000)[0].size
    assert 0.47 * all_ts.shape[1] < kept <= 0.56 * all_ts.shape[1]


def test_replica_b_lags_by_an_offset_of_its_own_a_series():
    cfg = dict(small(HA), jitter_s=0)
    g = gen(cfg)
    ts, _ = g.scrapes(np.random.default_rng(1), 0, 6)
    assert ((0 <= g.offset) & (g.offset < 15_000)).all()
    assert g.offset.shape == (64, 1)
    assert len(set(g.offset[:, 0].tolist())) > 32
    for i in range(64):
        a = [15_000 * (j + 1) for j in range(6)]
        b = [15_000 * j + int(g.offset[i, 0]) for j in range(6)]
        assert ts[i].tolist() == sorted(a + b)
    # the offsets are the file's, not the seed's
    other = gen(cfg)
    other.scrapes(np.random.default_rng(2), 0, 6)
    np.testing.assert_array_equal(g.offset, other.offset)


def test_the_rows_width_follows_the_replicas():
    """`replicas` is the file's: three scrapers hand out rows of 3k
    samples, of which a dedup keeps a third, and 15 s still add what
    dash8k's 15 s add."""
    g = gen(dict(small(HA), replicas=3))
    ts, vals = g.scrapes(np.random.default_rng(3), 1_794_000_000_000, 240)
    assert ts.shape == vals.shape == (64, 3 * 240) and g.offset.shape == (64, 2)
    assert (np.diff(ts, axis=1) >= 0).all()
    assert (np.diff(vals, axis=1) >= 0).all()
    per_15s = vals[:, -1] / (ts[:, -1] - ts[:, 0]) * 15_000
    assert 22 < per_15s.mean() < 26
    kept = harness.reference.dedup(ts[0], vals[0], 15_000)[0].size
    assert 0.31 * ts.shape[1] < kept <= 0.4 * ts.shape[1]


def test_a_seed_gives_the_same_arrays_at_any_anchor():
    cfg = small(HA)
    a = harness.Dataset(cfg, 11, at(2026, 11, 15, 9, 0))
    b = harness.Dataset(cfg, 11, at(2026, 11, 1, 3, 0))
    assert a.t_start != b.t_start
    np.testing.assert_array_equal(a.vals, b.vals)
    np.testing.assert_array_equal(a.ts - a.t_start, b.ts - b.t_start)
    (ta, va), (tb, vb) = a.advance(), b.advance()
    np.testing.assert_array_equal(va, vb)
    np.testing.assert_array_equal(ta - a.t_start, tb - b.t_start)
    c = harness.Dataset(cfg, 12, at(2026, 11, 15, 9, 0))
    assert (c.vals != a.vals).any()


def test_the_bulk_ends_under_the_first_window_and_ticks_under_theirs():
    cfg = small(HA)
    data = harness.Dataset(cfg, 3_800_000_023, at(2026, 11, 15, 9, 0))
    assert data.ts.shape == (64, 2 * 240)
    jitter = 2000
    assert data.ts.max() < data.end <= data.ts.max() + data.step + jitter
    assert data.ts.min() >= data.t_start - data.scrape - jitter
    for _ in range(5):
        end = data.end
        ts, _ = data.advance()
        assert ts.min() >= end - jitter and ts.max() <= data.end + jitter
    ts, vals = data.snapshot(5)
    assert (np.diff(ts, axis=1) >= 0).all()
    # exposition: one line a sample, both replicas' samples in one text
    text = harness.exposition(data.keys, *data.tails[0]).decode()
    assert text.count("\n") == 64 * 8
