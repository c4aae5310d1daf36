"""Deployment generator `histogram`: one Prometheus histogram an instance,
its `le` buckets as series of one metric (labels le, instance, job).  An
instance serves requests at a rate drawn once from U[rate_min, rate_max)
a second; a scrape interval brings Poisson(rate x interval) of them, each
with a log-normal latency whose median is the job's (latency_median_s x
latency_median_growth ** job) and whose sigma is latency_sigma.  A scrape
reads all buckets of an instance at ONE timestamp (+-jitter_s an
instance), as cumulative whole-number counts: along `le` at every scrape,
and in time.  Everything comes from the rng the harness seeds."""

from __future__ import annotations

import math

import numpy as np


class Deployment:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.buckets = list(cfg["buckets"])
        self.n_inst = int(cfg["instances"])
        if self.n_inst * len(self.buckets) != int(cfg["series"]):
            raise ValueError("histogram: series is not instances x buckets")
        self.scrape_ms = int(cfg["scrape_interval_s"] * 1000)
        self.jitter_ms = int(cfg["jitter_s"] * 1000)
        job = np.arange(self.n_inst) % int(cfg["jobs"])
        # [instances, buckets]: the share of an instance's requests that
        # falls in each bucket (le_prev, le]
        mu = np.log(cfg["latency_median_s"]) + \
            job * np.log(cfg["latency_median_growth"])
        z = (np.log([float(b) for b in self.buckets[:-1]])[None, :] -
             mu[:, None]) / cfg["latency_sigma"]
        cdf = np.hstack([np.vectorize(math.erf)(z / math.sqrt(2)) / 2 + 0.5,
                         np.ones((self.n_inst, 1))])
        self.share = np.diff(cdf, axis=1, prepend=0.0)
        self.rate = None
        self.last = np.zeros((self.n_inst, len(self.buckets)), dtype=np.int64)

    def labels(self) -> list:
        c = self.cfg
        return [{"__name__": c["metric"], "le": le, "instance": f"host-{i}",
                 "job": f"job-{i % c['jobs']}"}
                for i in range(self.n_inst) for le in self.buckets]

    def scrapes(self, rng, t_from: int, k: int):
        """k scrapes of every series after t_from: ([S, k] int64 ms sorted
        along k, [S, k] float64 cumulative bucket counts), series
        instance-major, an instance's buckets in ascending `le`."""
        if self.rate is None:
            self.rate = rng.uniform(self.cfg["rate_min"],
                                    self.cfg["rate_max"], self.n_inst)
        ts = t_from + (np.arange(k, dtype=np.int64) + 1)[None, :] * \
            self.scrape_ms + rng.integers(-self.jitter_ms,
                                          self.jitter_ms + 1,
                                          (self.n_inst, k))
        ts.sort(axis=1)
        requests = rng.poisson(self.rate[:, None] * self.scrape_ms / 1e3,
                               (self.n_inst, k))
        # [instances, k, buckets] -> [instances, buckets, k]
        hits = rng.multinomial(requests, self.share[:, None, :]
                               ).transpose(0, 2, 1)
        vals = self.last[:, :, None] + np.cumsum(np.cumsum(hits, axis=1),
                                                 axis=2)
        self.last = vals[:, :, -1]
        n_b = len(self.buckets)
        return (np.repeat(ts, n_b, axis=0),
                vals.reshape(self.n_inst * n_b, k).astype(np.float64))
