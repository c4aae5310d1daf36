"""The node_exporter fleet's cpu counters (`node_cpu`) and the
configuration `node-cpu8k` beside `dash8k`: the labels and the panel's
series, the ticks a cpu spends against the time that passed, whole
numbers, the reboots, the rebased ranges against 2^24, the file's kept
values, the plain reference over a reboot against a brute-force double
loop, the helper's stream, and a traced run of the cell on the CPU with
float32 tiles."""

import calendar
import os

import numpy as np
import pytest

import harness
import reference

BENCH = harness.HERE
CPU = harness.load_json(BENCH, "configs", "node-cpu8k.json")
DASH = harness.load_json(BENCH, "configs", "dash8k.json")
NET = harness.load_json(BENCH, "configs", "node-net8k.json")
NOW = calendar.timegm((2026, 11, 15, 9, 0, 0)) * 1000
SEED = 4_600_000_017
TEXT = CPU["queries"]["panel"]["templates"][0]


def small(**kw) -> dict:
    return dict(CPU, **dict(dict(hosts=4, series=256, instances=4,
                                 range_h=1), **kw))


def gen(cfg: dict):
    return harness.load_module("deployments",
                               cfg["deployment"]).Deployment(cfg)


def test_the_file_keeps_dash8ks_values():
    for key in ("range_h", "scrape_interval_s", "jitter_s", "query_step_s",
                "window_s", "limits", "series"):
        assert CPU[key] == DASH[key], key
    assert CPU["guarantees"][:3] == DASH["guarantees"]
    assert CPU["guarantees"][3] == NET["guarantees"][3]
    for key in ("uptime_days_min", "uptime_days_max", "reboots_share",
                "reboots_hours", "job"):
        assert CPU[key] == NET[key], key
    assert CPU["architecture"] is None and len(CPU["source"]) <= 200
    assert CPU["series"] == CPU["hosts"] * CPU["cpus"] * 8 == 8192
    assert CPU["user_hz"] == 100
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "node-cpu8k"]
    assert entry["source"] == CPU["source"]
    assert sorted(entry["reduced"]) == sorted(CPU["reduced"])
    (cell,) = [w for w in bench["workloads"] if w["config"] == "node-cpu8k"]
    assert cell["chips"] == 1 and cell["traffic"] == "refresh"
    assert CPU["queries"]["panel"]["templates"] == [
        "avg by (instance)(sum by (instance, cpu)(rate(node_cpu_seconds_total"
        '{mode=~"idle|iowait|steal"}[5m])))']


def test_the_labels_are_the_cpu_collectors():
    labels = gen(CPU).labels()
    assert len(labels) == 8192
    assert {l["job"] for l in labels} == {"node"}
    assert {l["cpu"] for l in labels} == {str(c) for c in range(8)}
    assert [l["mode"] for l in labels[:8]] == list(CPU["modes"])
    assert len({l["instance"] for l in labels}) == 128
    assert labels[75] == {"__name__": "node_cpu_seconds_total", "cpu": "1",
                          "instance": "host-1:9100", "job": "node",
                          "mode": "idle"}
    ast = reference.parse(TEXT)
    assert ast == ("avg", ("instance",), (
        "sum", ("instance", "cpu"), (
            "rollup", "rate", "node_cpu_seconds_total",
            {"mode": ("=~", "idle|iowait|steal")}, 300_000)))
    idx = reference.select(labels, *reference.selector(ast))
    assert idx.size == 3072
    assert {labels[i]["mode"] for i in idx} == {"idle", "iowait", "steal"}
    assert len(reference.row_labels(ast[2], labels)) == 1024
    assert len(reference.row_labels(ast, labels)) == 128
    with pytest.raises(ValueError):
        gen(dict(CPU, series=8000))
    with pytest.raises(ValueError):
        gen(dict(CPU, modes=CPU["modes"][::-1]))


@pytest.fixture(scope="module")
def bulk():
    """The whole fleet's bulk, as a run draws it."""
    return harness.Dataset(CPU, SEED, NOW)


def _host_reboots(vals: np.ndarray, hosts: int) -> np.ndarray:
    """[hosts, N - 1]: whether a host's counters fell at a scrape."""
    return (np.diff(vals, axis=1) < 0).reshape(hosts, -1,
                                               vals.shape[1] - 1).any(axis=1)


def test_a_cpus_counters_grow_by_the_elapsed_ticks(bulk):
    """Whole ticks; between two scrapes a cpu's 8 counters gain together
    round(dt x 100), dt the seconds between its host's two scrape times,
    wherever the host did not reboot; every cpu's shares add up to 1."""
    vals, ts = bulk.vals, bulk.ts
    assert vals.shape == (8192, 1440)
    assert (vals == np.round(vals)).all() and vals.min() >= 0
    assert vals.max() < 90 * 8.64e6 + 1
    per_cpu = np.diff(vals, axis=1).reshape(1024, 8, -1).sum(axis=1)
    host_ts = ts.reshape(128, 64, -1)
    assert (host_ts == host_ts[:, :1]).all()    # one scrape time a host
    dt = np.diff(ts[::8], axis=1)
    want = np.round(dt / 10.0)
    rebooted = np.repeat(_host_reboots(vals, 128), 8, axis=0)
    assert (per_cpu == want)[~rebooted].all()
    assert (want >= 1100).all() and (want <= 1900).all()
    np.testing.assert_allclose(bulk.gen.shares.sum(axis=1), 1.0, rtol=1e-12)
    assert (bulk.gen.shares > 0).all()


def test_a_reboot_restarts_all_64_of_a_hosts_counters(bulk):
    """1 host in 64 reboots in 6 h, so the bulk of 128 hosts holds some
    2: all 64 counters of the host fall at one scrape, to the ticks of
    that scrape alone."""
    g = bulk.gen
    assert 1 - (1 - g.hazard) ** (6 * 3600 / 15) == pytest.approx(1 / 64)
    drops = (np.diff(bulk.vals, axis=1) < 0).reshape(128, 64, -1)
    fell = drops.any(axis=1)
    assert (drops.all(axis=1) == fell).all()
    assert 1 <= fell.sum() <= 8
    h, j = np.argwhere(fell)[0]
    after = bulk.vals[64 * h:64 * h + 64, j + 1].reshape(8, 8).sum(axis=1)
    assert (after <= 1900).all()


def test_every_rebased_6h_range_without_a_reboot_is_narrow(bulk):
    """A counter gains at most 21,600 s x 100 = 2.16e6 ticks in 6 h, so
    every row of a host that did not reboot rebases under 2^24 over the
    bulk, with the 5 m window and the jitter (2.2e6); a reboot drops the
    host's rows by their whole value, past 2^24 where the host had been
    up for days."""
    vals = bulk.vals
    fell = np.repeat(_host_reboots(vals, 128).any(axis=1), 64)
    rebased = np.abs(vals - vals[:, :1]).max(axis=1)
    assert (rebased[~fell] < 2.2e6).all() and rebased[~fell].max() > 1e6
    assert rebased[~fell].max() < 2 ** 24
    assert (rebased[fell].max() >= 2 ** 24) == \
        (vals[fell].max() >= 2 ** 24)


def test_ticks_reboot_as_the_bulk_does():
    cfg = small(reboots_share=0.999, reboots_hours=1)
    g = gen(cfg)
    rng = np.random.default_rng(SEED)
    g.scrapes(rng, 0, 240)
    fell = 0
    for t in range(30):
        last = g.last.copy()
        _, vals = g.scrapes(rng, (240 + 4 * t) * 15_000, 4)
        fell += int((vals[:, 0] < last).sum())
    assert fell > 0


def _max_prev_interval(ts: np.ndarray) -> int:
    si = int(np.quantile(np.diff(ts[-21:]).astype(np.float64), 0.6))
    for bound, mul in ((2000, 4.0), (4000, 2.0), (8000, 1.0), (16000, 0.5),
                       (32000, 0.25)):
        if si <= bound:
            return si + int(si * mul)
    return si + si // 8


def _brute_rate(ts, vals, grid, window):
    """One series, one grid step at a time: resets removed as upstream's
    removeCounterResets does, then the window's last value less the one
    at or before its start (within maxPrevInterval), else its first."""
    v, corr, prev = [], 0.0, vals[0]
    for x in vals:
        if x < prev:
            corr += prev - x if (prev - x) * 8 < prev else prev
        prev = x
        v.append(x + corr)
    mpi = _max_prev_interval(ts)
    out = []
    for t in grid:
        inside = [j for j in range(len(ts)) if t - window < ts[j] <= t]
        before = [j for j in range(len(ts)) if ts[j] <= t - window]
        if not inside:
            out.append(np.nan)
            continue
        if before and ts[before[-1]] > t - window - mpi:
            first = before[-1]
        elif len(inside) >= 2:
            first = inside[0]
        else:
            out.append(np.nan)
            continue
        last = inside[-1]
        dt = (ts[last] - ts[first]) / 1e3
        out.append((v[last] - v[first]) / dt if dt > 0 else np.nan)
    return np.array(out)


def test_the_reference_answers_the_panel_over_reboots_as_a_double_loop():
    """Hosts that reboot inside the range (hazard raised for the test):
    reference.evaluate against the brute force, cpu by cpu (the sum of
    the three modes' rates) and host by host (the mean of its cpus), at
    1e-12."""
    data = harness.Dataset(small(hosts=2, series=128, instances=2,
                                 reboots_share=0.9, reboots_hours=1),
                           SEED, NOW)
    ts, vals = data.ts, data.vals
    assert _host_reboots(vals, 2)[:, 60:].any()
    grid = np.arange(data.start, data.end + 1, data.step, dtype=np.int64)
    kind, labels, got = reference.evaluate(reference.parse(TEXT),
                                           data.labels, ts, vals, grid)
    assert kind == "rows" and len(labels) == 2
    for lab, mine in zip(labels, got):
        cpus = []
        for c in range(8):
            rows = [i for i, l in enumerate(data.labels)
                    if l["instance"] == lab["instance"]
                    and l["cpu"] == str(c)
                    and l["mode"] in ("idle", "iowait", "steal")]
            per = np.stack([_brute_rate(ts[i], vals[i], grid, 300_000)
                            for i in rows])
            n = (~np.isnan(per)).sum(axis=0)
            cpus.append(np.where(n > 0, np.nansum(per, axis=0), np.nan))
        cpus = np.stack(cpus)
        n = (~np.isnan(cpus)).sum(axis=0)
        want = np.where(n > 0, np.nansum(cpus, axis=0) / np.maximum(n, 1),
                        np.nan)
        np.testing.assert_allclose(mine, want, rtol=1e-12, atol=0)


def test_the_helpers_ticks_equal_the_in_process_twins():
    producer = harness.load_module("traffic", "producer")
    cfg = small(reboots_share=0.9, reboots_hours=1)
    twin = harness.Dataset(cfg, SEED, NOW)
    data = harness.Dataset(cfg, SEED, NOW)
    twin.advance(110)
    data.advance(110)
    helper = producer.Producer()
    try:
        helper.start(data.hand_over())
        for _ in range(6):
            ts, vals, text = helper.take()
            want_ts, want_vals = twin.advance()
            np.testing.assert_array_equal(ts, want_ts)
            np.testing.assert_array_equal(vals, want_vals)
            assert text == harness.exposition(twin.keys, want_ts, want_vals)
    finally:
        helper.close()


def test_a_traced_run_on_float32_tiles_reads_the_nested_aggregate(
        monkeypatch):
    """The cell's traced run rehearsed at 8 hosts on the CPU, the engine
    forced to float32 tiles as the chip's are: `correct`, every metric
    the cell lists read, `nested_device_pct` 100 (the inner aggregate
    fused on the device every query) and `outer_aggr_ms_per_query` above
    0."""
    import jax

    import run
    from victoriametrics_tpu.query import tpu_engine
    monkeypatch.setattr(tpu_engine, "auto_value_dtype", lambda: np.float32)
    bench = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "node-cpu8k.refresh")
    mix = harness.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    cfg = dict(CPU, hosts=8, series=512, instances=8, range_h=1)
    result, _ = run.measure(bench, cell, cfg, mix, SEED, 2.0, True,
                            jax.devices()[:1], {})
    assert result["correct"], result["checks"]
    due = {m["name"] for m in bench["per_layer"]
           if cell["name"] in m.get("workloads", [cell["name"]])
           and m["source"] != "device_trace"}
    assert due - set(result["metrics"]) == set()
    assert result["metrics"]["nested_device_pct"]["value"] == 100.0
    assert result["metrics"]["outer_aggr_ms_per_query"]["value"] > 0.0
