"""Deployment generator `node_net`: the node_exporter fleet's network
byte counters.  `hosts` hosts (label `instance`), each with `interfaces`
interfaces (`device` eth0, eth1, ...) and the two directions of the netdev
collector (`metrics`: receive, then transmit), all under one `job`; series
i is host i // (2 x interfaces), direction (i // interfaces) % 2,
interface i % interfaces.

Each series has a mean rate r, log-uniform in [rate_min, rate_max) bytes
a second, and each host an uptime at the bulk's start, uniform in
[uptime_days_min, uptime_days_max) days: the series starts at
round(r x uptime).  Every scrape adds round(r x scrape_interval x
U[0.5, 1.5)) whole bytes.  A host reboots before a scrape with the
hazard under which a share `reboots_share` of the hosts reboots in
`reboots_hours` hours, in the bulk and in the ticks alike: all of its
counters then restart from that scrape's bytes, the bytes counted since
boot.  Scrape times are `counters`' (the grid of scrape_interval_s with
+-jitter_s).  The rates, the uptimes and every draw after them come from
the rng the harness seeds: the first call (the bulk) draws the rates and
uptimes."""

from __future__ import annotations

import numpy as np


class Deployment:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.hosts = int(cfg["hosts"])
        self.per_host = 2 * int(cfg["interfaces"])
        self.n = self.hosts * self.per_host
        if self.n != int(cfg["series"]):
            raise ValueError("series has to be hosts x 2 x interfaces")
        self.scrape_ms = int(cfg["scrape_interval_s"] * 1000)
        self.jitter_ms = int(cfg["jitter_s"] * 1000)
        share, hours = cfg["reboots_share"], cfg["reboots_hours"]
        self.hazard = 1.0 - (1.0 - share) ** (
            cfg["scrape_interval_s"] / (hours * 3600.0))
        self.rate = None        # [S] mean bytes a second, drawn at the bulk
        self.last = None        # [S] the counters' newest values

    def labels(self) -> list:
        c = self.cfg
        k = int(c["interfaces"])
        return [{"__name__": c["metrics"][(i // k) % 2],
                 "device": f"eth{i % k}",
                 "instance": f"host-{i // self.per_host}:9100",
                 "job": c["job"]} for i in range(self.n)]

    def scrapes(self, rng, t_from: int, k: int):
        """k scrapes of every series after t_from: ([S, k] int64 ms sorted
        along k, [S, k] float64 counter values, whole bytes)."""
        c = self.cfg
        if self.rate is None:
            lo, hi = np.log(c["rate_min"]), np.log(c["rate_max"])
            self.rate = np.exp(rng.uniform(lo, hi, self.n))
            uptime_s = 86400.0 * rng.uniform(
                c["uptime_days_min"], c["uptime_days_max"], self.hosts)
            self.last = np.round(self.rate * np.repeat(uptime_s,
                                                       self.per_host))
        ts = t_from + (np.arange(k, dtype=np.int64) + 1)[None, :] * \
            self.scrape_ms + rng.integers(-self.jitter_ms,
                                          self.jitter_ms + 1, (self.n, k))
        ts.sort(axis=1)
        inc = np.round(self.rate[:, None] * (self.scrape_ms / 1000) *
                       rng.uniform(0.5, 1.5, (self.n, k)))
        boots = np.repeat(rng.random((self.hosts, k)) < self.hazard,
                          self.per_host, axis=0)
        run = np.cumsum(inc, axis=1)
        # the newest reboot at or before each scrape (-1: none in this call)
        boot = np.maximum.accumulate(
            np.where(boots, np.arange(k)[None, :], -1), axis=1)
        since = np.take_along_axis(run, np.maximum(boot - 1, 0), axis=1)
        vals = np.where(boot < 0, self.last[:, None] + run,
                        run - np.where(boot > 0, since, 0.0))
        self.last = vals[:, -1].copy()
        return ts, vals
