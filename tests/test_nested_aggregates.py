"""An aggregate over an aggregate (the `node-cpu8k` deployment's panel,
the node-mixin's CPU utilisation): `avg`, `max` and `sum by (instance)`
over `sum by (instance, cpu)(rate(...{mode=~"idle|iowait|steal"}[5m]))`,
on the benchmark's own `node_cpu` generator at 4 hosts x 2 cpus x 8
modes, one host rebooting in the tick after the bulk.

(a) each outer aggregate, served by the float32 device engine and by the
    host evaluator, equals the plain float64 reference
    (`benchmark/reference.py`, loaded by path), before the tick and in
    the refresh after it;
(b) on the device the inner aggregate is served fused: before the tick
    from a narrow tile (every rebased range under 2^24), after it from a
    wide one, since the reboot's drop declines the resident window's
    append and the cold rebuild holds the rows' increments; every query
    counts one `vm_device_nested_aggr_total{inner=}` by where the inner
    ran and the 8 inner rows in `vm_device_nested_rows_total`, and its
    outer reduction lands in the phase `eval:outer_aggr` while the inner
    fetches under the phases it has at top level, the family still
    partitioning the root's wall;
(c) `vm_serve_route_total{route=}` counts the route a `query_range`
    takes: the nested panel the result cache, its inner aggregate alone
    the resident window the nested panel's inner left;
(d) the new members and the phase are exported at 0 from import.
"""

import importlib.util
import json
import os
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
from victoriametrics_tpu.utils import fasttime, flightrec
from victoriametrics_tpu.utils import metrics as metricslib


def _by_path(*parts):
    path = os.path.join(REPO, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "nested_" + parts[-1].split(".")[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _by_path("reference.py")
node_cpu = _by_path("deployments", "node_cpu.py")
with open(os.path.join(REPO, "benchmark", "configs", "node-cpu8k.json")) as f:
    CFG = dict(json.load(f), hosts=4, cpus=2, series=64, instances=4,
               reboots_share=0.0)

STEP, SCRAPE, K = 60_000, 15_000, 480
REBOOT_HOST, REBOOT_AT = 2, 1           # the tick's second scrape
INNER = ('sum by (instance, cpu)(rate(node_cpu_seconds_total'
         '{mode=~"idle|iowait|steal"}[5m]))')
FAMILY = "vm_query_phase_seconds_total{"
WALL = "vm_query_wall_seconds_total"
NESTED = "vm_device_nested_aggr_total{"
ROWS = "vm_device_nested_rows_total"
HITS = "vm_device_window_cache_hits_total"
WIDE = 'vm_device_wide_rows_total{path="device"}'
DECLINE = metricslib.format_name("vm_device_roll_declines_total",
                                 {"reason": te.WIDE_APPEND})
ROUTE = "vm_serve_route_total{"
OUTER_PHASE = FAMILY + 'phase="eval:outer_aggr"}'


def _metrics() -> dict:
    out = {}
    for line in metricslib.REGISTRY.write_prometheus().splitlines():
        if line.startswith((FAMILY, WALL, NESTED, ROWS, HITS, ROUTE, WIDE,
                            DECLINE)):
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


@pytest.fixture(scope="module")
def fleet():
    """Two hours of the fleet ending 12 h ago, and the tick after them: 4
    scrapes of every series, host 2 rebooting at the second (its 16
    counters restart from that scrape's ticks, the generator's rule)."""
    gen = node_cpu.Deployment(CFG)
    rng = np.random.default_rng(4_600_000_019)
    t0 = (fasttime.unix_ms() - 14 * 3_600_000) // STEP * STEP
    ts, vals = gen.scrapes(rng, t0, K)
    ts2, vals2 = gen.scrapes(rng, t0 + K * SCRAPE, 4)
    rows = slice(REBOOT_HOST * 16, REBOOT_HOST * 16 + 16)
    vals2[rows, REBOOT_AT:] -= vals2[rows, REBOOT_AT - 1:REBOOT_AT]
    return gen.labels(), (ts, vals), (ts2, vals2), \
        (t0 + K * SCRAPE) // STEP * STEP


def _write(store, labels, ts, vals) -> None:
    store.add_rows([(lab, int(t), float(v))
                    for lab, tss, vs in zip(labels, ts, vals)
                    for t, v in zip(tss, vs)])


def _served(store, engine, q: str, start: int, end: int) -> dict:
    """{instance: values} of one query, evaluated under a request root."""
    with flightrec.phase("serve:other", root=True):
        got = exec_query(EvalConfig(start=start, end=end, step=STEP,
                                    storage=store, tpu=engine), q)
    return {r.metric_name.to_dict()["instance"]: np.asarray(r.values)
            for r in got}


def _phase_at_fetch(store) -> set:
    """The innermost query phase open at each of `store`'s fetches."""
    seen = set()
    for name in ("search_columns", "search_series"):
        def fetch(*a, _f=getattr(store, name), **kw):
            seen.add(flightrec._tls.stack[-1].name)
            return _f(*a, **kw)
        setattr(store, name, fetch)
    return seen


def _want(labels, ts, vals, q: str, start: int, end: int) -> dict:
    grid = np.arange(start, end + 1, STEP, dtype=np.int64)
    kind, rows, out = reference.evaluate(reference.parse(q), labels, ts,
                                         vals, grid)
    assert kind == "rows"
    return {r["instance"]: out[i] for i, r in enumerate(rows)}


def test_the_fleet_is_narrow_until_one_reboot(fleet):
    """The bulk rebases under 2^24 and never falls; in the tick host 2's
    16 counters fall by their whole value, past 2^24 for at least one."""
    labels, (ts, vals), (ts2, vals2), _ = fleet
    assert len(labels) == 64 and labels[9] == {
        "__name__": "node_cpu_seconds_total", "cpu": "1",
        "instance": "host-0:9100", "job": "node", "mode": "nice"}
    assert (np.diff(vals, axis=1) >= 0).all()
    assert (vals - vals[:, :1]).max() < 2 ** 24
    both = np.concatenate([vals, vals2], axis=1)
    drops = (np.diff(both, axis=1) < 0).reshape(4, 16, -1)
    assert drops.any(axis=(1, 2)).tolist() == [False, False, True, False]
    assert drops[REBOOT_HOST, :, K + REBOOT_AT - 1].all()
    assert vals[REBOOT_HOST * 16:REBOOT_HOST * 16 + 16, -1].max() >= 2 ** 24
    assert (both == np.round(both)).all() and both.min() >= 0


@pytest.mark.parametrize("outer,way", [
    (o, w) for o in ("avg", "max", "sum") for w in ("device", "host")])
def test_an_aggregate_over_an_aggregate_answers_as_float64(
        tmp_path, fleet, outer, way):
    """Tolerance: 1e-9 on the host (float64 throughout); 2e-6 on the
    float32 engine, which reads 1.4e-7 to 2.1e-7 here (rebased values,
    then after the reboot increments rounded once each), where the
    reference over the raw tick counters rounded to float32 is off by
    1.5e-3 to 2.6e-3, and a reset left in by far more."""
    labels, (ts, vals), (ts2, vals2), end = fleet
    q = f"{outer} by (instance)({INNER})"
    engine = te.TPUEngine(value_dtype=np.float32, min_series=2) \
        if way == "device" else None
    rtol = 2e-6 if way == "device" else 1e-9
    store = Storage(str(tmp_path / "s"))
    fetched_in = _phase_at_fetch(store)
    try:
        _write(store, labels, ts, vals)
        store.force_flush()
        for n, (tss, vs) in enumerate(((ts, vals), (np.concatenate(
                [ts, ts2], axis=1), np.concatenate([vals, vals2], axis=1)))):
            if n:
                _write(store, labels, ts2, vals2)
            stop = end + n * STEP
            m0 = _metrics()
            got = _served(store, engine, q, stop - 60 * STEP, stop)
            m1 = _metrics()
            want = _want(labels, tss, vs, q, stop - 60 * STEP, stop)
            assert sorted(got) == sorted(want) == \
                [f"host-{h}:9100" for h in range(4)]
            for inst, row in want.items():
                np.testing.assert_allclose(got[inst], row, rtol=rtol,
                                           equal_nan=True)
            d = {k: v - m0.get(k, 0.0) for k, v in m1.items()}
            assert d[NESTED + f'inner="{way}"}}'] == 1
            assert d[NESTED + 'inner="{}"}}'.format(
                "host" if way == "device" else "device")] == 0
            assert d[ROWS] == 8
            assert d[OUTER_PHASE] > 0
            fam = sum(v for k, v in d.items() if k.startswith(FAMILY))
            assert fam == pytest.approx(d[WALL], rel=0.01)
            # the refresh's append meets the reboot: a decline, and the
            # cold rebuild a wide tile whose 24 rows the device reads
            wide = n and way == "device"
            assert d[DECLINE] == d[WIDE] / 24 == wide
            assert d.get(HITS, 0.0) == 0
        # the inner aggregate fetched under its own phases, not the outer's
        assert fetched_in and "eval:outer_aggr" not in fetched_in
    finally:
        store.close()


def test_the_serving_route_is_counted(tmp_path, fleet):
    """Over HTTP: the nested panel with nocache=1 is `direct`; without it
    the result cache misses, then hits; the inner aggregate asked alone
    after them finds the resident window the nested panel's inner left
    (device_window_ready looks through no outer aggregate)."""
    if not native.available():
        pytest.skip("needs native lib")
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.httpapi.server import HTTPServer
    labels, (ts, vals), _, end = fleet
    store = Storage(str(tmp_path / "s"))
    srv = HTTPServer("127.0.0.1", 0)
    PrometheusAPI(store, tpu_engine=te.TPUEngine(
        value_dtype=np.float32, min_series=2)).register(srv)
    srv.start()
    try:
        _write(store, labels, ts, vals)
        store.force_flush()
        c = Client(srv.port)
        q = f"avg by (instance)({INNER})"
        seen = []
        for text, nocache in ((q, 1), (q, 0), (q, 0), (INNER, 0)):
            m0 = _metrics()
            args = dict(query=text, start=(end - 60 * STEP) / 1e3,
                        end=end / 1e3, step=STEP // 1000)
            if nocache:
                args["nocache"] = 1
            code, body = c.get("/api/v1/query_range", **args)
            assert code == 200, body
            for _ in range(2000):   # the root closes after the last byte
                m1 = _metrics()
                if m1[WALL] > m0[WALL]:
                    break
                time.sleep(0.005)
            seen.append([k[len(ROUTE):-1] for k in m1 if k.startswith(ROUTE)
                         and m1[k] - m0[k] == 1])
        assert seen == [['route="direct"'], ['route="cache_miss"'],
                        ['route="cache_hit"'], ['route="window"']]
    finally:
        srv.stop()
        store.close()


def test_the_members_and_the_phase_read_0_from_import():
    code = """
from victoriametrics_tpu.httpapi import prometheus_api
from victoriametrics_tpu.query import tpu_engine
from victoriametrics_tpu.utils import flightrec, metrics
assert "eval:outer_aggr" in flightrec.QUERY_PHASES
text = metrics.REGISTRY.write_prometheus()
lines = dict(l.rsplit(" ", 1) for l in text.splitlines()
             if l and not l.startswith("#"))
names = ['vm_device_nested_aggr_total{inner="device"}',
         'vm_device_nested_aggr_total{inner="host"}',
         "vm_device_nested_rows_total",
         'vm_query_phase_seconds_total{phase="eval:outer_aggr"}']
names += ['vm_serve_route_total{route="%s"}' % r for r in (
    "window", "cache_hit", "cache_partial", "cache_miss", "direct")]
for name in names:
    assert float(lines[name]) == 0.0, name
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr
