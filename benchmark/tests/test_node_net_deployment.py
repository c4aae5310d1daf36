"""The node_exporter fleet's byte counters (`node_net`) and the
configuration `node-net8k` beside `dash8k`: the labels, the magnitudes
against 2^24 and 2^53, the reboots, the file's kept values, the plain
reference over a reboot against a brute-force double loop, the helper's
stream, and a traced run of the cell on the CPU with float32 tiles."""

import calendar
import os

import numpy as np
import pytest

import harness
import reference

BENCH = harness.HERE
NET = harness.load_json(BENCH, "configs", "node-net8k.json")
DASH = harness.load_json(BENCH, "configs", "dash8k.json")
NOW = calendar.timegm((2026, 11, 15, 9, 0, 0)) * 1000
SEED = 4_400_000_017


def small(**kw) -> dict:
    return dict(NET, hosts=8, series=64, instances=8, range_h=1, **kw)


def gen(cfg: dict):
    return harness.load_module("deployments",
                               cfg["deployment"]).Deployment(cfg)


def test_the_file_keeps_dash8ks_values():
    for key in ("range_h", "scrape_interval_s", "jitter_s", "query_step_s",
                "window_s", "limits", "series"):
        assert NET[key] == DASH[key], key
    assert NET["guarantees"][:3] == DASH["guarantees"]
    assert len(NET["guarantees"]) == 4
    assert "removeCounterResets" in NET["guarantees"][3]
    assert NET["architecture"] is None and len(NET["source"]) <= 200
    assert NET["series"] == NET["hosts"] * 2 * NET["interfaces"] == 8192
    assert NET["reboots_share"] == 1 / 64 and NET["reboots_hours"] == 6
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "node-net8k"]
    assert entry["source"] == NET["source"]
    assert sorted(entry["reduced"]) == sorted(NET["reduced"])
    texts = NET["queries"]["panel"]["templates"]
    assert texts == [f"sum by (instance)(rate({m}[5m]))"
                     for m in NET["metrics"]]


def test_the_labels_are_the_netdev_collectors():
    labels = gen(NET).labels()
    assert len(labels) == 8192
    assert {l["job"] for l in labels} == {"node"}
    assert {l["device"] for l in labels} == {f"eth{e}" for e in range(4)}
    assert len({l["instance"] for l in labels}) == 1024
    assert labels[9] == {"__name__": "node_network_receive_bytes_total",
                         "device": "eth1", "instance": "host-1:9100",
                         "job": "node"}
    for text in NET["queries"]["panel"]["templates"]:
        ast = reference.parse(text)
        idx = reference.select(labels, *reference.selector(ast))
        assert idx.size == 4096
        assert len(reference.row_labels(ast, labels)) == 1024
    with pytest.raises(ValueError):
        gen(dict(NET, series=8000))


@pytest.fixture(scope="module")
def bulk():
    """The whole fleet's bulk, as a run draws it."""
    data = harness.Dataset(NET, SEED, NOW)
    return data


def test_every_series_is_wide_and_f64_exact(bulk):
    """Every series' rebased range over the bulk reaches 2^24 (the
    quietest interface gains 1e4 B/s x 6 h = 2.2e8), no value reaches
    2^53, and every value is a whole number of bytes."""
    vals = bulk.vals
    assert vals.shape == (8192, 1440)
    assert (np.abs(vals - vals[:, :1]).max(axis=1) >= 2 ** 24).all()
    assert vals.max() < 2 ** 53 and vals.min() >= 0
    assert (vals == np.round(vals)).all()
    g = bulk.gen
    assert ((1e4 <= g.rate) & (g.rate < 1e8)).all()
    assert (vals[:, 0] <= g.rate * 90 * 86400 + g.rate * 15 * 1.5).all()


def test_hosts_reboot_at_the_stated_rate_and_inside_the_bulk(bulk):
    """A reboot drops all eight counters of its host at one scrape; 1 host
    in 64 reboots in 6 h, so the bulk of 1024 hosts holds some 16."""
    g = bulk.gen
    assert 1 - (1 - g.hazard) ** (6 * 3600 / 15) == pytest.approx(1 / 64)
    drops = (np.diff(bulk.vals, axis=1) < 0).reshape(1024, 8, -1)
    assert (drops.all(axis=1) == drops.any(axis=1)).all()
    n = int(drops.all(axis=1).sum())
    assert 4 <= n <= 40
    # after a reboot a counter holds only the bytes since boot
    h, j = np.argwhere(drops.all(axis=1))[0]
    after = bulk.vals[8 * h:8 * h + 8, j + 1]
    assert (after <= g.rate[8 * h:8 * h + 8] * 15 * 1.5 + 1).all()


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


@pytest.mark.parametrize("text", NET["queries"]["panel"]["templates"])
def test_the_reference_answers_a_panel_over_reboots_as_a_double_loop(text):
    """Hosts that reboot inside the range (hazard raised for the test) and
    a partial reset planted in one row: reference.evaluate against the
    brute force, host by host, at 1e-12."""
    data = harness.Dataset(small(reboots_share=0.9, reboots_hours=1),
                           SEED, NOW)
    ts, vals = data.ts, data.vals.copy()
    row = 5 if "transmit" in text else 1
    vals[row, 120:] -= np.round(0.05 * vals[row, 119]) + \
        (vals[row, 120] - vals[row, 119])
    drops = np.diff(vals, axis=1) < 0
    assert drops[:, 60:].any() and drops[row, 119]
    grid = np.arange(data.start, data.end + 1, data.step, dtype=np.int64)
    kind, labels, got = reference.evaluate(reference.parse(text),
                                           data.labels, ts, vals, grid)
    assert kind == "rows" and len(labels) == 8
    metric = reference.selector(reference.parse(text))[0]
    for lab, mine in zip(labels, got):
        rows = [i for i, l in enumerate(data.labels)
                if l["instance"] == lab["instance"]
                and l["__name__"] == metric]
        per = np.stack([_brute_rate(ts[i], vals[i], grid, 300_000)
                        for i in rows])
        n = (~np.isnan(per)).sum(axis=0)
        want = np.where(n > 0, np.nansum(per, axis=0), np.nan)
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


def test_a_traced_run_on_float32_tiles_reads_the_hand_off(monkeypatch):
    """The cell's traced run rehearsed at 64 hosts on the CPU, the engine
    forced to float32 tiles as the chip's are: `correct`, every metric the
    cell lists read, `wide_device_pct` 0 and `wide_host_ms_per_query`
    above 0."""
    import jax

    import run
    from victoriametrics_tpu.query import tpu_engine
    monkeypatch.setattr(tpu_engine, "auto_value_dtype", lambda: np.float32)
    bench = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "node-net8k.refresh")
    mix = harness.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    cfg = dict(NET, hosts=64, series=512, instances=64, range_h=1)
    result, _ = run.measure(bench, cell, cfg, mix, SEED, 2.0, True,
                            jax.devices()[:1], {})
    assert result["correct"], result["checks"]
    due = {m["name"] for m in bench["per_layer"]
           if cell["name"] in m.get("workloads", [cell["name"]])
           and m["source"] != "device_trace"}
    assert due - set(result["metrics"]) == set()
    assert result["metrics"]["wide_device_pct"]["value"] == 0.0
    assert result["metrics"]["wide_host_ms_per_query"]["value"] > 0.0
