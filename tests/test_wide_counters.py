"""Byte counters at their real sizes on the served path with float32 device
tiles (the `node-net8k` deployment's panel): every series' rebased range
passes 2^24, so the tile's value plane holds reset-corrected increments
and the device serves the panel's `rate` from them.

(a) both panel texts over HTTP `query_range` equal an f64 brute force of
    `rate` with upstream's counter-reset rule (a reboot and a partial
    reset among the samples): at 1e-9 on a float64 engine, which pins the
    f64 arithmetic, and at 1e-6 on the float32 engine's increments;
(b) the device's share is counted once a query: `vm_device_wide_rows_total
    {path="device"}` grows by the series the query reads, `{path="host"}`
    and the phase `eval:wide_host` stay where they were, the family still
    partitioning the root's wall;
(c) both members, the phase and the wide tiles' decline reasons are
    exported at 0 from import;
(d) a resident window whose append would pass 2^24 declines the advance
    and counts the reason once; the cold rebuild is a wide tile, served on
    the device.
"""

import json
import subprocess
import sys
import time

import numpy as np
import pytest

from tests.apptest_helpers import REPO, Client
from victoriametrics_tpu import native
from victoriametrics_tpu.query import tpu_engine as te
from victoriametrics_tpu.query.exec import exec_query
from victoriametrics_tpu.query.types import EvalConfig
from victoriametrics_tpu.storage.storage import Storage
from victoriametrics_tpu.utils import fasttime
from victoriametrics_tpu.utils import metrics as metricslib

STEP = 60_000
SCRAPE = 15_000
WINDOW = 300_000
HOSTS, DEVICES, K = 8, 4, 240
METRICS = ("node_network_receive_bytes_total",
           "node_network_transmit_bytes_total")
PANELS = {m.split("_")[2]: f"sum by (instance)(rate({m}[5m]))"
          for m in METRICS}
REBOOT_HOST, REBOOT_AT = 3, 150        # all 8 of its counters restart
PARTIAL_ROW, PARTIAL_AT = 5, 100       # a drop under 1/8 of the value
FAMILY = "vm_query_phase_seconds_total{"
WALL = "vm_query_wall_seconds_total"
WIDE = "vm_device_wide_rows_total{"
MEMBER = FAMILY + 'phase="eval:wide_host"}'


def _metrics() -> dict:
    out = {}
    for line in metricslib.REGISTRY.write_prometheus().splitlines():
        if line.startswith((FAMILY, WALL, WIDE)):
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


def _labels() -> list:
    """Host h, direction d, interface e: row (h x 2 + d) x 4 + e."""
    return [{"__name__": METRICS[d], "device": f"eth{e}",
             "instance": f"host-{h}:9100", "job": "node"}
            for h in range(HOSTS) for d in range(2) for e in range(DEVICES)]


def _byte_counters(t0: int):
    """[S, K] timestamps and whole-byte values: bases 1e9-1e14, rates
    1e4-1e8 B/s (log-uniform), a reboot of one host and one partial
    reset."""
    rng = np.random.default_rng(44_000_000_007)
    n = HOSTS * 2 * DEVICES
    ts = t0 + np.arange(1, K + 1)[None, :] * SCRAPE + \
        rng.integers(-2000, 2001, (n, K))
    ts.sort(axis=1)
    rate = np.exp(rng.uniform(np.log(1e4), np.log(1e8), n))
    base = np.round(np.exp(rng.uniform(np.log(1e9), np.log(1e14), n)))
    inc = np.round(rate[:, None] * 15 * rng.uniform(0.5, 1.5, (n, K)))
    vals = base[:, None] + np.cumsum(inc, axis=1)
    rows = slice(REBOOT_HOST * 8, REBOOT_HOST * 8 + 8)
    vals[rows, REBOOT_AT:] = np.cumsum(inc[rows, REBOOT_AT:], axis=1)
    drop = np.round(0.05 * vals[PARTIAL_ROW, PARTIAL_AT - 1])
    vals[PARTIAL_ROW, PARTIAL_AT:] -= drop + inc[PARTIAL_ROW, PARTIAL_AT]
    assert (np.diff(vals[PARTIAL_ROW]) < 0).sum() == 1
    return ts, vals


def _max_prev_interval(ts: np.ndarray) -> int:
    """Upstream's headroom over a series' scrape interval (the 0.6
    quantile of its last 20 intervals)."""
    si = int(np.quantile(np.diff(ts[-21:]).astype(np.float64), 0.6))
    for bound, mul in ((2000, 4.0), (4000, 2.0), (8000, 1.0), (16000, 0.5),
                       (32000, 0.25)):
        if si <= bound:
            return si + int(si * mul)
    return si + si // 8


def _brute_rate(ts: np.ndarray, vals: np.ndarray, grid) -> np.ndarray:
    """rate over (t - 5m, t] one sample at a time: resets removed as
    upstream's removeCounterResets does (a drop under 1/8 of the value
    adds the drop, a larger one the value before it), the window's last
    value less the one at or before its start (within maxPrevInterval),
    else less the window's first."""
    v = np.empty(vals.size)
    corr, prev = 0.0, vals[0]
    for j, x in enumerate(vals):
        if x < prev:
            corr += prev - x if (prev - x) * 8 < prev else prev
        prev = x
        v[j] = x + corr
    mpi = _max_prev_interval(ts)
    out = np.full(len(grid), np.nan)
    for g, t in enumerate(grid):
        inside = [j for j in range(ts.size) if t - WINDOW < ts[j] <= t]
        if not inside:
            continue
        before = [j for j in range(ts.size) if ts[j] <= t - WINDOW]
        if before and ts[before[-1]] > t - WINDOW - mpi:
            first = before[-1]
        elif len(inside) >= 2:
            first = inside[0]
        else:
            continue
        last = inside[-1]
        if ts[last] > ts[first]:
            out[g] = (v[last] - v[first]) / ((ts[last] - ts[first]) / 1e3)
    return out


def _text(labels, ts, vals) -> bytes:
    rows = []
    for lab, tss, vs in zip(labels, ts.tolist(),
                            vals.astype(np.int64).tolist()):
        key = lab["__name__"] + "{" + ",".join(
            f'{k}="{v}"' for k, v in sorted(lab.items())
            if k != "__name__") + "}"
        rows.extend(f"{key} {v} {t}" for v, t in zip(vs, tss))
    return ("\n".join(rows) + "\n").encode()


def _serve(tmp_path_factory, dtype):
    """An hour of the fleet's byte counters ending 12 h ago, imported and
    queried over HTTP with a `dtype` device engine attached."""
    if not native.available():
        pytest.skip("needs native lib")
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.httpapi.server import HTTPServer
    t0 = (fasttime.unix_ms() - 13 * 3_600_000) // STEP * STEP
    ts, vals = _byte_counters(t0)
    s = Storage(str(tmp_path_factory.mktemp("wide") / "s"))
    srv = HTTPServer("127.0.0.1", 0)
    PrometheusAPI(s, tpu_engine=te.TPUEngine(value_dtype=dtype,
                                             min_series=2)).register(srv)
    srv.start()
    c = Client(srv.port)
    code, body = c.post("/api/v1/import/prometheus", _text(_labels(), ts, vals))
    assert code in (200, 204), body
    c.force_flush()
    end = (t0 + K * SCRAPE) // STEP * STEP
    try:
        yield c, ts, vals, end
    finally:
        srv.stop()
        s.close()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    yield from _serve(tmp_path_factory, np.float32)


@pytest.fixture(scope="module")
def served64(tmp_path_factory):
    yield from _serve(tmp_path_factory, np.float64)


def _ask(c, q: str, start: int, end: int) -> dict:
    """One query_range (nocache=1: every ask is evaluated); returns once
    the request's root has closed, so its counters have landed."""
    wall0 = _metrics()[WALL]
    code, body = c.get("/api/v1/query_range", query=q, start=start / 1e3,
                       end=end / 1e3, step=STEP // 1000, nocache=1)
    assert code == 200, body
    res = json.loads(body)
    assert res["status"] == "success" and not res.get("isPartial")
    for _ in range(2000):
        if _metrics()[WALL] > wall0:
            return res
        time.sleep(0.005)
    raise AssertionError("the request's root never closed")


def test_the_data_are_wide_with_a_reboot_and_a_partial_reset():
    ts, vals = _byte_counters(0)
    rebased = vals - vals[:, :1]
    assert (np.abs(rebased).max(axis=1) >= 2 ** 24).all()
    assert vals.max() < 2 ** 53 and vals[:, 0].min() >= 1e9
    drops = np.diff(vals, axis=1) < 0
    assert drops.sum() == 8 + 1
    assert drops[REBOOT_HOST * 8:REBOOT_HOST * 8 + 8, REBOOT_AT - 1].all()
    d = vals[PARTIAL_ROW, PARTIAL_AT - 1] - vals[PARTIAL_ROW, PARTIAL_AT]
    assert 0 < d * 8 < vals[PARTIAL_ROW, PARTIAL_AT - 1]


@pytest.mark.parametrize("panel,engine,rtol", [
    pytest.param(p, e, r, id=p + ("" if e == "served64" else "-float32"))
    for e, r in (("served64", 1e-9), ("served", 1e-6))
    for p in sorted(PANELS)])
def test_a_panel_equals_the_f64_brute_force(request, panel, engine, rtol):
    """Tolerance 1e-9 on a float64 engine: float64 throughout (what is
    left is the order of a 4-term sum, 1e-16 a term, and the answer's
    decimal text). 1e-6 on the float32 engine: one f32 rounding of each
    increment (6e-8), a window sum of some 20 of them, the 4-term group
    sum and the answer's own f32 rounding read at most 2e-7 here; a
    float32 evaluation of the rebased values is off by 1e-3 and more,
    and a reset left in by far more."""
    c, ts, vals, end = request.getfixturevalue(engine)
    start = end - 40 * STEP
    grid = np.arange(start, end + 1, STEP, dtype=np.int64)
    res = _ask(c, PANELS[panel], start, end)
    got = {r["metric"]["instance"]:
           {round(t * 1000): float(v) for t, v in r["values"]}
           for r in res["data"]["result"]}
    labels = _labels()
    assert sorted(got) == sorted({l["instance"] for l in labels})
    d = METRICS.index(f"node_network_{panel}_bytes_total")
    for h in range(HOSTS):
        rows = [(h * 2 + d) * 4 + e for e in range(DEVICES)]
        per = np.stack([_brute_rate(ts[i], vals[i], grid) for i in rows])
        n = (~np.isnan(per)).sum(axis=0)
        want = np.where(n > 0, np.nansum(per, axis=0), np.nan)
        mine = got[f"host-{h}:9100"]
        assert sorted(mine) == [int(t) for t in grid[~np.isnan(want)]]
        np.testing.assert_allclose([mine.get(int(t), np.nan) for t in grid],
                                   want, rtol=rtol, atol=0)


def test_the_host_takes_every_wide_row_once(served):
    """One query over 32 series: the fused entry serves the tile's
    increments on the device; its 32 rows are counted there once, none on
    the host, `eval:wide_host` takes nothing and the family still sums to
    the root's wall."""
    c, _, _, end = served
    m0 = _metrics()
    _ask(c, PANELS["receive"], end - 40 * STEP, end)
    m1 = _metrics()
    assert m1[WIDE + 'path="device"}'] - m0[WIDE + 'path="device"}'] == 32
    assert m1[WIDE + 'path="host"}'] == m0[WIDE + 'path="host"}']
    fam = {k: v - m0[k] for k, v in m1.items() if k.startswith(FAMILY)}
    wall = m1[WALL] - m0[WALL]
    assert wall > 0 and fam.get(MEMBER, 0.0) == 0.0
    assert sum(fam.values()) == pytest.approx(wall, rel=0.01)


def test_the_members_and_the_phase_read_0_from_import():
    code = f"""
from victoriametrics_tpu.query import tpu_engine
from victoriametrics_tpu.utils import flightrec, metrics
assert "eval:wide_host" in flightrec.QUERY_PHASES
text = metrics.REGISTRY.write_prometheus()
lines = dict(l.rsplit(" ", 1) for l in text.splitlines()
             if l and not l.startswith("#"))
for name in ({WIDE!r} + 'path="host"}}', {WIDE!r} + 'path="device"}}',
             {MEMBER!r}) + tuple(
        'vm_device_roll_declines_total{{reason="' + r + '"}}'
        for r in (tpu_engine.WIDE_APPEND, tpu_engine.WIDE_REWIND,
                  tpu_engine.WIDE_NONFINITE)):
    assert float(lines[name]) == 0.0, name
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_a_wide_append_declines_the_window_once(tmp_path):
    """A resident window of narrow counters, then a tick in which one
    counter gains 1e8 bytes: the append's rebased range passes 2^24, the
    advance declines with that reason counted once, and the cold rebuild
    (a wide tile, its plane the rows' increments) is served on the device,
    its 16 rows counted there, answering as a float64 engine does."""
    t0 = (fasttime.unix_ms() - 3 * 3_600_000) // STEP * STEP
    q = "sum by (g)(rate(hx[5m]))"
    store = Storage(str(tmp_path / "s"))
    try:
        def write(t_from, k, jump=0.0):
            rows = []
            for i in range(16):
                tss = t_from + SCRAPE * np.arange(1, k + 1)
                lab = {"__name__": "hx", "i": str(i), "g": f"g{i % 4}"}
                rows += [(lab, int(t), 1e9 + 3.0 * (t - t0) / 1000 +
                          (jump if i == 0 else 0.0)) for t in tss]
            store.add_rows(rows)

        def query(engine, end):
            got = exec_query(EvalConfig(start=end - 20 * STEP, end=end,
                                        step=STEP, storage=store,
                                        tpu=engine), q)
            return {r.metric_name.marshal(): np.asarray(r.values)
                    for r in got}

        write(t0 - 40 * STEP, 160)
        store.force_flush()
        engine = te.TPUEngine(value_dtype=np.float32, min_series=4)
        query(engine, t0)
        reason = metricslib.format_name("vm_device_roll_declines_total",
                                        {"reason": te.WIDE_APPEND})
        d0 = metricslib.REGISTRY.counter(reason).get()
        w0 = _metrics()
        write(t0, 4, jump=1e8)
        got = query(engine, t0 + STEP)
        assert engine.last_roll_decline == te.WIDE_APPEND
        assert metricslib.REGISTRY.counter(reason).get() == d0 + 1
        w1 = _metrics()
        for path, rows in (("host", 0), ("device", 16)):
            name = WIDE + f'path="{path}"}}'
            assert w1[name] == w0[name] + rows, path
        want = query(te.TPUEngine(min_series=4), t0 + STEP)
        assert set(got) == set(want) and got
        for k in got:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-6,
                                       equal_nan=True)
    finally:
        store.close()
