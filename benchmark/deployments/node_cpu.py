"""Deployment generator `node_cpu`: the node_exporter fleet's cpu
collector, `node_cpu_seconds_total`.  `hosts` hosts (label `instance`),
each with `cpus` cpus (label `cpu`, "0" ..) and the eight modes of the
Linux cpu collector (`modes`, label `mode`), all under one `job`; series
i is host i // (cpus x 8), cpu (i // 8) % cpus, mode i % 8.

The counters are kept in USER_HZ ticks (`user_hz` a second), as
/proc/stat keeps them: node_exporter divides by USER_HZ, and the
harness's exposition writes whole numbers (the configuration's
`assumed` says why).  A host's cpus are scraped at one jittered time
(`counters`' grid of scrape_interval_s with +-jitter_s).  Between two
scrapes a cpu spends round(dt x user_hz) ticks, dt the elapsed seconds,
and a multinomial splits them over its eight modes by the cpu's shares,
so a cpu's counters grow by exactly the elapsed ticks.

The shares: each host draws a busy share in [busy_min, busy_max), each
cpu scatters round its host's by U[-busy_scatter, busy_scatter) (kept in
[0.02, 0.96]); of a cpu's busy share irq takes U[0, 0.02), softirq
U[0.005, 0.05), system U[0.1, 0.3) and nice U[0, 0.05), user the rest;
iowait U[0.001, 0.02) and steal U[0, 0.01) of the whole, idle what is
left.  Each host has an uptime at the bulk's start, uniform in
[uptime_days_min, uptime_days_max) days, whose ticks a multinomial splits
by the same shares.  A host reboots before a scrape with `node_net`'s
hazard and rule: a share `reboots_share` of the hosts reboots in
`reboots_hours` hours, in the bulk and in the ticks alike, and all of its
cpus x 8 counters restart from that scrape's ticks.

Over 6 h a counter gains at most 21,600 s x 100 = 2.16e6 ticks (with the
5 m window and the jitter, under 2.2e6), so a range that holds no reboot
rebases under 2^24 and its tile is narrow: the f32 rebased device path.
A reboot inside the range drops the host's counters by their whole value
before it (up to 90 days x 8.64e6 ticks), which passes 2^24 unless the
host had been up for under two days, and makes the tile wide: the
counter family then reads that tile's increments on the device.  The
shares, the uptimes and every draw after them come from the rng the
harness seeds: the first call (the bulk) draws the shares and uptimes."""

from __future__ import annotations

import numpy as np

# /proc/stat's order, as the cpu collector exports the modes
MODES = ("user", "nice", "system", "idle", "iowait", "irq", "softirq",
         "steal")


class Deployment:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        if tuple(cfg["modes"]) != MODES:
            raise ValueError(f"modes has to be {MODES}")
        self.hosts = int(cfg["hosts"])
        self.cpus = int(cfg["cpus"])
        self.per_host = self.cpus * len(MODES)
        self.n = self.hosts * self.per_host
        if self.n != int(cfg["series"]):
            raise ValueError("series has to be hosts x cpus x 8 modes")
        self.scrape_ms = int(cfg["scrape_interval_s"] * 1000)
        self.jitter_ms = int(cfg["jitter_s"] * 1000)
        self.hz = int(cfg["user_hz"])
        share, hours = cfg["reboots_share"], cfg["reboots_hours"]
        self.hazard = 1.0 - (1.0 - share) ** (
            cfg["scrape_interval_s"] / (hours * 3600.0))
        self.shares = None      # [hosts x cpus, 8] drawn at the bulk
        self.prev_ts = None     # [hosts] the newest scrape's time
        self.last = None        # [S] the counters' newest values

    def labels(self) -> list:
        c = self.cfg
        k = len(MODES)
        return [{"__name__": c["metric"], "cpu": str((i // k) % self.cpus),
                 "instance": f"host-{i // self.per_host}:9100",
                 "job": c["job"], "mode": MODES[i % k]}
                for i in range(self.n)]

    def _draw_shares(self, rng) -> np.ndarray:
        """[hosts x cpus, 8] each cpu's share of its time in each mode."""
        c = self.cfg
        n = self.hosts * self.cpus
        host = rng.uniform(c["busy_min"], c["busy_max"], self.hosts)
        busy = np.clip(np.repeat(host, self.cpus) + rng.uniform(
            -c["busy_scatter"], c["busy_scatter"], n), 0.02, 0.96)
        irq = busy * rng.uniform(0.0, 0.02, n)
        softirq = busy * rng.uniform(0.005, 0.05, n)
        system = busy * rng.uniform(0.1, 0.3, n)
        nice = busy * rng.uniform(0.0, 0.05, n)
        user = busy - irq - softirq - system - nice
        iowait = rng.uniform(0.001, 0.02, n)
        steal = rng.uniform(0.0, 0.01, n)
        idle = 1.0 - busy - iowait - steal
        return np.stack([user, nice, system, idle, iowait, irq, softirq,
                         steal], axis=1)

    def scrapes(self, rng, t_from: int, k: int):
        """k scrapes of every series after t_from: ([S, k] int64 ms sorted
        along k, [S, k] float64 counter values, whole ticks)."""
        c = self.cfg
        if self.shares is None:
            self.shares = self._draw_shares(rng)
            uptime = np.round(86400.0 * self.hz * rng.uniform(
                c["uptime_days_min"], c["uptime_days_max"], self.hosts))
            self.last = rng.multinomial(
                np.repeat(uptime, self.cpus).astype(np.int64),
                self.shares).reshape(-1).astype(np.float64)
            self.prev_ts = np.full(self.hosts, t_from, dtype=np.int64)
        ts = t_from + (np.arange(k, dtype=np.int64) + 1)[None, :] * \
            self.scrape_ms + rng.integers(-self.jitter_ms,
                                          self.jitter_ms + 1, (self.hosts, k))
        ts.sort(axis=1)
        dt = np.diff(np.concatenate([self.prev_ts[:, None], ts], axis=1),
                     axis=1)
        self.prev_ts = ts[:, -1].copy()
        ticks = np.round(dt * (self.hz / 1000.0)).astype(np.int64)
        # [hosts x cpus, k, 8] -> [S, k]: a cpu's ticks split over its modes
        inc = rng.multinomial(np.repeat(ticks, self.cpus, axis=0),
                              self.shares[:, None, :])
        inc = inc.transpose(0, 2, 1).reshape(self.n, k).astype(np.float64)
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
        return np.repeat(ts, self.per_host, axis=0), vals
