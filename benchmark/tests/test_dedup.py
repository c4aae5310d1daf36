"""Dedup in the plain reference (`reference.dedup`, `dedup_rows`) against
a brute-force double loop; where the harness applies it (a configuration
that states `dedup_interval_s`) and where not (every older one); and the
flags a configuration starts its server with."""

import numpy as np
import pytest

import harness
import reference

BENCH = harness.HERE
BENCHMARK = harness.load_json(harness.ROOT, "BENCHMARK.json")
NOW = 1_794_000_000_000
OLDER = ["dash32k", "dash8k", "histo8k", "dash32k-4chip", "tsbs-devops-512"]
FOUR = ["-httpListenAddr=127.0.0.1:0", "-search.tpuBackend",
        "-search.maxQueryDuration=300s"]


def brute(ts, vals, interval):
    """One survivor a window (m - 1, m] x interval: of the window's
    samples the one with the highest timestamp; of several at that
    timestamp the largest value.  A double loop."""
    out = []
    for m in sorted({-(-int(t) // interval) for t in ts}):
        best = None
        for t, v in zip(ts, vals):
            if (m - 1) * interval < t <= m * interval:
                if best is None or (t, v) > best:
                    best = (int(t), float(v))
        out.append(best)
    return (np.array([t for t, _ in out], dtype=np.int64),
            np.array([v for _, v in out]))


def test_a_window_of_three_a_multiple_and_a_tie():
    ts = np.array([15_001, 20_000, 30_000, 30_001, 44_000, 44_000, 45_000,
                   45_000, 60_000])
    vals = np.array([1.0, 2.0, 3.0, 4.0, 9.0, 7.0, 5.0, 6.0, 8.0])
    got_ts, got = reference.dedup(ts, vals, 15_000)
    # (15, 30] holds three samples and ends on one exactly at a multiple;
    # (30, 45] ends in a tie at 45 000, the larger value wins; 60 000
    # closes its own window
    assert got_ts.tolist() == [30_000, 45_000, 60_000]
    assert got.tolist() == [3.0, 6.0, 8.0]
    assert got_ts.dtype == np.int64 and got.dtype == np.float64


@pytest.mark.parametrize("seed", [1, 2, 3_800_000_011])
@pytest.mark.parametrize("interval", [15_000, 1, 60_000])
def test_dedup_equals_the_double_loop_on_seeded_rows(seed, interval):
    rng = np.random.default_rng(seed)
    for _ in range(20):
        n = int(rng.integers(1, 60))
        # coarse times: equal timestamps and exact multiples are common
        ts = np.sort(rng.integers(1, 40, n) * 5_000 +
                     rng.choice([0, 0, 1, -1, 2_500], n))
        vals = rng.integers(0, 9, n).astype(np.float64)
        want_ts, want = brute(ts, vals, interval)
        got_ts, got = reference.dedup(ts, vals, interval)
        np.testing.assert_array_equal(got_ts, want_ts)
        np.testing.assert_array_equal(got, want)
        # what dedup kept, dedup keeps
        again_ts, again = reference.dedup(got_ts, got, interval)
        np.testing.assert_array_equal(again_ts, got_ts)
        np.testing.assert_array_equal(again, got)


def test_an_empty_row_stays_empty():
    ts, vals = reference.dedup(np.array([], dtype=np.int64), np.array([]), 15)
    assert ts.size == vals.size == 0


def test_dense_rows_read_as_their_survivors():
    """A rollup over `dedup_rows`' filled rows equals the rollup over
    each row's survivors alone."""
    rng = np.random.default_rng(5)
    base = 1_700_000_000_000
    ts = np.sort(base + rng.integers(0, 3_600_000, (6, 400)), axis=1)
    ts[0, 100:] += 1_800_000          # a row that keeps fewer: a gap
    ts[0].sort()
    vals = np.cumsum(rng.integers(0, 25, ts.shape), axis=1).astype(float)
    d_ts, d_vals = reference.dedup_rows(ts, vals, 15_000)
    widths = [reference.dedup(t, v, 15_000)[0].size for t, v in zip(ts, vals)]
    assert d_ts.shape == d_vals.shape == (6, max(widths))
    assert len(set(widths)) > 1 and (np.diff(d_ts, axis=1) >= 0).all()
    grid = np.arange(base + 600_000, base + 5_400_000, 60_000, dtype=np.int64)
    for func in reference.ROLLUPS:
        dense = reference.rollup(func, d_ts, d_vals, grid, 300_000)
        for i, (t, v) in enumerate(zip(ts, vals)):
            one = reference.rollup(func, *(a[None, :] for a in
                                           reference.dedup(t, v, 15_000)),
                                   grid, 300_000)
            np.testing.assert_array_equal(dense[i], one[0])


def small(config: str) -> dict:
    cfg = harness.load_json(harness.ROOT, next(
        c["file"] for c in BENCHMARK["configs"] if c["name"] == config))
    cfg.update({"counters": dict(series=64, instances=8, jobs=4, range_h=1),
                "counters_ha": dict(series=64, instances=8, jobs=4, range_h=1),
                "histogram": dict(series=96, instances=8, jobs=4, range_h=1),
                "tsbs_cpu": dict(hosts=8, range_h=14)}[cfg["deployment"]])
    return cfg


def first_query(cfg: dict) -> str:
    if "queries" in cfg:
        return cfg["queries"]["panel"]["templates"][0]
    return 'avg(avg_over_time({__name__=~"cpu_(usage_user)"}[1h])) ' \
           'by (__name__, hostname)'


def test_only_the_ha_pair_states_an_interval():
    stated = {c["name"] for c in BENCHMARK["configs"] if "dedup_interval_s" in
              harness.load_json(harness.ROOT, c["file"])}
    assert stated == {"dash8k-ha2"}
    assert sorted(OLDER + ["dash8k-ha2"]) == sorted(
        c["name"] for c in BENCHMARK["configs"])


@pytest.mark.parametrize("config", OLDER)
def test_an_older_configuration_is_read_as_the_parent_read_it(config,
                                                              monkeypatch):
    """No `dedup_interval_s`, no dedup: `visible` hands out `snapshot`'s
    own arrays, `query_work` counts every sample in the range (a mask
    over all of them), and nothing calls the reference's dedup."""
    def never(*a):
        raise AssertionError("dedup called for a configuration without it")
    monkeypatch.setattr(reference, "dedup_rows", never)
    monkeypatch.setattr(reference, "dedup", never)
    cfg = small(config)
    data = harness.Dataset(cfg, 3_800_000_013, NOW)
    assert data.dedup == 0
    assert data.visible(0)[0] is data.ts and data.visible(0)[1] is data.vals
    q = first_query(cfg)
    step = int(cfg["query_step_s"] * 1000) if "query_step_s" in cfg \
        else 3_600_000
    n_tails = 0
    if cfg.get("ingests", True):
        data.advance()
        n_tails = 1
    asked = dict(query=q, start=data.start, end=data.end, n_tails=n_tails,
                 step=step)
    ast = reference.parse(q)
    idx = reference.select(data.labels, *reference.selector(ast))
    ts, vals = data.snapshot(n_tails)
    lo = asked["start"] - reference.window_of(ast)
    want = int(((ts[idx] > lo) & (ts[idx] <= asked["end"])).sum())
    assert harness.query_work(data, asked)["samples"] == want > 0
    # the comparison reads the same arrays: the reference against itself
    kind, labels, ref = reference.evaluate(
        ast, data.labels, ts, vals,
        np.arange(asked["start"], asked["end"] + 1, step, dtype=np.int64))
    body = harness.json.dumps({"status": "success", "isPartial": False,
                               "data": {"result": [
        {"metric": l, "values": [
            [t / 1000, repr(float(v))] for t, v in zip(
                range(asked["start"], asked["end"] + 1, step), row)
            if not np.isnan(v)]}
        for l, row in zip(labels, ref) if not np.isnan(row).all()]}}).encode()
    numbers = harness.check_answers(data, [dict(asked, body=body)])
    assert numbers["rel_err"] == 0 and numbers["nan_mismatch"] == 0
    assert numbers["series_mismatch"] == 0 and numbers["values"] > 0


def test_the_ha_pair_is_read_through_dedup():
    cfg = small("dash8k-ha2")
    data = harness.Dataset(cfg, 3_800_000_017, NOW)
    assert data.dedup == 15_000
    data.advance()
    raw_ts, raw_vals = data.snapshot(1)
    ts, vals = data.visible(1)
    assert raw_ts.shape[1] == 2 * (240 + 4) and ts.shape[1] < raw_ts.shape[1]
    for i in (0, 17, 63):
        want_ts, want = brute(raw_ts[i], raw_vals[i], 15_000)
        np.testing.assert_array_equal(ts[i, -want_ts.size:], want_ts)
        np.testing.assert_array_equal(vals[i, -want.size:], want)
    # query_work counts the survivors, about half of what was written
    q = first_query(cfg)
    asked = dict(query=q, start=data.start, end=data.end, n_tails=1)
    lo = asked["start"] - 300_000
    survivors = int(((ts > lo) & (ts <= asked["end"])).sum())
    written = int(((raw_ts > lo) & (raw_ts <= asked["end"])).sum())
    assert harness.query_work(data, asked)["samples"] == survivors
    assert 0.45 * written < survivors < 0.62 * written


@pytest.mark.parametrize("seed", [3_800_000_031, 5, 2_900_000_033])
def test_the_survivors_are_counted_as_a_mask_over_them_counts(seed):
    """`samples_between` under a dedup interval (the bulk's survivors by
    two binary searches a row, the tails' by their windows' newest)
    against a mask over `visible`: any rows, any range, with and without
    ticks, a range's ends on and off the interval's multiples."""
    cfg = small("dash8k-ha2")
    data = harness.Dataset(cfg, seed, NOW)
    for _ in range(6):
        data.advance()
    rng = np.random.default_rng(seed)
    for n_tails in (0, 1, 3, 6):
        ts, _ = data.visible(n_tails)
        for _ in range(6):
            idx = np.sort(rng.choice(64, int(rng.integers(1, 65)),
                                     replace=False))
            lo = int(data.t_start + rng.integers(0, 3_000_000))
            hi = lo + int(rng.integers(1, 1_200_000))
            if rng.random() < 0.5:      # as a query's: on the grid
                lo, hi = lo // 60_000 * 60_000, hi // 60_000 * 60_000
            want = int(((ts[idx] > lo) & (ts[idx] <= hi)).sum())
            assert data.samples_between(idx, lo, hi, n_tails) == want
        # a range that ends inside the newest tail and one past it
        end = int(ts.max())
        for hi in (end - 7_000, end, end + 60_000):
            every = np.arange(64)
            want = int(((ts > hi - 600_000) & (ts <= hi)).sum())
            assert data.samples_between(every, hi - 600_000, hi,
                                        n_tails) == want > 0


@pytest.mark.parametrize("config",
                         sorted(c["name"] for c in BENCHMARK["configs"]))
def test_the_reference_dedups_by_the_interval_the_server_is_started_with(
        config):
    """`dedup_interval_s` restates what `server_flags` says, for the
    reference's side; the two have to agree in every configuration, and a
    configuration with neither has neither."""
    cfg = harness.load_json(harness.ROOT, next(
        c["file"] for c in BENCHMARK["configs"] if c["name"] == config))
    flags = [f.split("=", 1)[1] for f in cfg.get("server_flags", [])
             if f.startswith("-dedup.minScrapeInterval=")]
    if "dedup_interval_s" in cfg:
        assert flags == [f"{cfg['dedup_interval_s']}s"]
    else:
        assert flags == []


@pytest.mark.parametrize("config", OLDER + ["dash8k-ha2"])
def test_a_configuration_starts_the_server_it_states(config, monkeypatch,
                                                     tmp_path):
    """The five older configurations start vmsingle with the four flags
    every run passed before `server_flags` was read; the HA pair adds
    its dedup interval, after them."""
    from victoriametrics_tpu.apps import vmsingle
    seen = []

    class Stop(Exception):
        pass

    def parse_flags(argv):
        seen.append(list(argv))
        raise Stop
    monkeypatch.setattr(vmsingle, "parse_flags", parse_flags)
    cfg = harness.load_json(harness.ROOT, next(
        c["file"] for c in BENCHMARK["configs"] if c["name"] == config))
    with pytest.raises(Stop):
        harness.Server(str(tmp_path), cfg.get("server_flags", []))
    own = ["-dedup.minScrapeInterval=15s"] if config == "dash8k-ha2" else []
    assert seen == [[f"-storageDataPath={tmp_path}"] + FOUR + own]
    assert ("server_flags" in cfg) == bool(own)


@pytest.mark.parametrize("config", ["dash8k", "dash8k-ha2"])
def test_set_up_hands_the_servers_flags_over(config, monkeypatch, tmp_path):
    import run
    seen = []

    class Stop(Exception):
        pass

    def server(data_dir, flags=()):
        seen.append((data_dir, list(flags)))
        raise Stop
    monkeypatch.setattr(harness, "Server", server)
    cfg = harness.load_json(BENCH, "configs", config + ".json")
    with pytest.raises(Stop):
        run.set_up(cfg, {}, 1, str(tmp_path))
    assert seen == [(str(tmp_path), cfg.get("server_flags", []))]
