"""Deployment generator `counters_ha`: `counters`' series (its labels, to
the value) scraped by `replicas` identically configured scrapers that
write to one server, the HA pair upstream documents ("High availability":
the server is started with -dedup.minScrapeInterval = the scrape
interval and keeps one sample an interval).

Replica A scrapes series i at t_from + 15 s x j + jitter, j = 1 .. k, as
`counters` does; every further replica at t_from + 15 s x j + offset_i +
jitter, j = 0 .. k - 1, with offset_i uniform in [0, 15 s), drawn once a
series and replica from the file's `replica_seed`: a Prometheus replica
spreads its targets over the interval by a hash of its own.  So every
sample of a call lies within jitter_s of (t_from, t_from + k x 15 s], as
`counters`' do.  All replicas read ONE monotone counter: the row's
`replicas` x k samples are sorted by time and each adds a whole number
drawn uniformly from [0, max_increment / replicas), so 15 s add what
`counters`' 15 s add.  A sample that would lie before the series' newest
one of the call before (B's first under A's last: offset_i under twice
the jitter) is read at that one's time, so no sample is older than one
handed out before it; equal timestamps occur.  The jitters and the
increments come from the rng the harness seeds."""

from __future__ import annotations

import numpy as np


class Deployment:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.n = int(cfg["series"])
        self.replicas = int(cfg["replicas"])
        self.scrape_ms = int(cfg["scrape_interval_s"] * 1000)
        self.jitter_ms = int(cfg["jitter_s"] * 1000)
        self.offset = np.random.default_rng(cfg["replica_seed"]).integers(
            0, self.scrape_ms, (self.n, self.replicas - 1))
        self.last = np.zeros(self.n, dtype=np.int64)
        self.last_ts = np.full(self.n, np.iinfo(np.int64).min)

    def labels(self) -> list:
        c = self.cfg
        return [{"__name__": c["metric"], "idx": str(i),
                 "instance": f"host-{i % c['instances']}",
                 "job": f"job-{i % c['jobs']}"} for i in range(self.n)]

    def scrapes(self, rng, t_from: int, k: int):
        """k scrapes of every series by every replica after t_from:
        ([S, replicas x k] int64 ms sorted along the row, the same shape
        of float64 running counter values)."""
        grid = np.arange(k, dtype=np.int64) * self.scrape_ms
        width = self.replicas * k
        jitter = rng.integers(-self.jitter_ms, self.jitter_ms + 1,
                              (self.n, width))
        ts = t_from + jitter + np.concatenate(
            [np.broadcast_to(grid + self.scrape_ms, (self.n, k))] +
            [grid + self.offset[:, r:r + 1]
             for r in range(self.replicas - 1)], axis=1)
        ts.sort(axis=1)
        ts = np.maximum(ts, self.last_ts[:, None])
        vals = self.last[:, None] + np.cumsum(rng.integers(
            0, self.cfg["max_increment"] // self.replicas, (self.n, width)),
            axis=1)
        self.last, self.last_ts = vals[:, -1], ts[:, -1]
        return ts, vals.astype(np.float64)
