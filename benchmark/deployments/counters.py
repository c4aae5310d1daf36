"""Deployment generator `counters`: N counter series, one per (idx,
instance, job), scraped every scrape_interval_s with +-jitter_s of jitter;
each scrape adds a whole number drawn uniformly from [0, max_increment).
Everything comes from the rng the harness seeds."""

from __future__ import annotations

import numpy as np


class Deployment:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.n = int(cfg["series"])
        self.scrape_ms = int(cfg["scrape_interval_s"] * 1000)
        self.jitter_ms = int(cfg["jitter_s"] * 1000)
        self.last = np.zeros(self.n, dtype=np.int64)

    def labels(self) -> list:
        c = self.cfg
        return [{"__name__": c["metric"], "idx": str(i),
                 "instance": f"host-{i % c['instances']}",
                 "job": f"job-{i % c['jobs']}"} for i in range(self.n)]

    def scrapes(self, rng, t_from: int, k: int):
        """k scrapes of every series after t_from: ([S, k] int64 ms sorted
        along k, [S, k] float64 running counter values)."""
        ts = t_from + (np.arange(k, dtype=np.int64) + 1)[None, :] * \
            self.scrape_ms + rng.integers(-self.jitter_ms,
                                          self.jitter_ms + 1, (self.n, k))
        ts.sort(axis=1)
        vals = self.last[:, None] + np.cumsum(
            rng.integers(0, self.cfg["max_increment"], (self.n, k)), axis=1)
        self.last = vals[:, -1]
        return ts, vals.astype(np.float64)
