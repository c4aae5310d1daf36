"""Deployment generator `tsbs_cpu`: the fleet of TSBS's use case
`cpu-only` (github.com/timescale/tsbs, devops): `hosts` hosts, each
reporting the ten cpu gauges `cpu_<field>` as ten series that carry the
host's ten tags.  A scrape is ONE timestamp for the whole fleet, on the
exact grid of scrape_interval_s (TSBS advances one simulated clock); a
gauge is a random walk clamped to [0, 100]: it starts at U[0, 100), every
scrape adds N(0, 1), and what is written is kept to two decimals.

The fleet itself (which region, rack, service a host carries) is drawn
from the configuration's `tag_seed`, not from the run's seed: every run
loads the same series set and the run's seed draws the values (and, in
traffic/intervals.py, the hosts and intervals asked).  The tag values
and their counts are recalled from TSBS, no file of it is on this
machine: the configuration lists them under `assumed`.
"""

from __future__ import annotations

import numpy as np

FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice")
# region -> its datacenters
REGIONS = {"us-east-1": "abcde", "us-west-1": "ab", "us-west-2": "abc",
           "eu-west-1": "abc", "eu-central-1": "ab", "ap-southeast-1": "ab",
           "ap-southeast-2": "ab", "ap-northeast-1": "ac", "sa-east-1": "abc"}
OS = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ARCH = ("x64", "x86")
TEAMS = ("SF", "NYC", "LON", "CHI")
ENVIRONMENTS = ("production", "staging", "test")
RACKS, SERVICES, SERVICE_VERSIONS = 100, 20, 2


class Deployment:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.hosts = int(cfg["hosts"])
        self.n = self.hosts * len(FIELDS)
        self.scrape_ms = int(cfg["scrape_interval_s"] * 1000)
        if cfg["jitter_s"]:
            raise ValueError("tsbs_cpu: a scrape is one timestamp for "
                             "the fleet, jitter_s has to be 0")
        self.last = None        # [S] the walks' unrounded state

    def host_tags(self) -> list:
        """One tag set a host, drawn from the configuration's tag_seed."""
        rng = np.random.default_rng(self.cfg["tag_seed"])
        regions = list(REGIONS)
        out = []
        for i in range(self.hosts):
            region = regions[rng.integers(len(regions))]
            zones = REGIONS[region]
            out.append({
                "hostname": f"host_{i}", "region": region,
                "datacenter": region + zones[rng.integers(len(zones))],
                "rack": str(rng.integers(RACKS)),
                "os": OS[rng.integers(len(OS))],
                "arch": ARCH[rng.integers(len(ARCH))],
                "team": TEAMS[rng.integers(len(TEAMS))],
                "service": str(rng.integers(SERVICES)),
                "service_version": str(rng.integers(SERVICE_VERSIONS)),
                "service_environment":
                    ENVIRONMENTS[rng.integers(len(ENVIRONMENTS))]})
        return out

    def labels(self) -> list:
        """Host-major: a host's ten series side by side."""
        return [dict(tags, __name__="cpu_" + field)
                for tags in self.host_tags() for field in FIELDS]

    def scrapes(self, rng, t_from: int, k: int):
        """k scrapes of every series after t_from: ([S, k] int64 ms, the
        same row for every series, [S, k] float64 gauge values)."""
        if self.last is None:
            self.last = rng.uniform(0.0, 100.0, self.n)
        # [k, S] so that a scrape is one contiguous row; each row of the
        # drawn steps is overwritten by the walk's state after it
        walk = rng.standard_normal((k, self.n))
        x = self.last
        for j in range(k):
            x = np.clip(x + walk[j], 0.0, 100.0)
            walk[j] = x
        self.last = x
        ts = t_from + (np.arange(k, dtype=np.int64) + 1) * self.scrape_ms
        return (np.broadcast_to(ts, (self.n, k)).copy(),
                np.ascontiguousarray(np.round(walk.T, 2)))
