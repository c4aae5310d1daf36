"""The data generator: what a seed gives, it gives again; counters only
grow, over the bulk and the tails."""

import numpy as np

import harness

COUNTERS = dict(deployment="counters", metric="m", series=64, instances=2,
                jobs=4, scrape_interval_s=15, jitter_s=2, max_increment=50)


def scrapes(cfg, seed, k=40):
    gen = harness.load_module("deployments", cfg["deployment"]).Deployment(cfg)
    ts, vals = gen.scrapes(np.random.default_rng(seed), 1_000_000, k)
    more_ts, more = gen.scrapes(np.random.default_rng(seed + 1),
                                int(ts.max()), 4)
    return gen, np.hstack([ts, more_ts]), np.hstack([vals, more])


def test_a_seed_gives_the_same_sorted_growing_series(cfg=COUNTERS):
    gen, ts, vals = scrapes(cfg, 2_400_000_011)
    _, ts2, vals2 = scrapes(cfg, 2_400_000_011)
    assert (ts == ts2).all() and (vals == vals2).all()
    assert ts.shape == vals.shape == (cfg["series"], 44)
    assert len(gen.labels()) == cfg["series"]
    assert (np.diff(ts[:, :40], axis=1) >= 0).all()
    assert (np.diff(vals, axis=1) >= 0).all()       # counters, tails too

