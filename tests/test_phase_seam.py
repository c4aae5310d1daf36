"""The phase seam (utils/flightrec.phase): self times partition a
request root's wall, one exit feeds the ring, the cost tracker, the
query-phase counter family and the profiler's host plane.  Structural
assertions only — no wall-clock thresholds."""

import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tests.apptest_helpers import REPO, Client
from victoriametrics_tpu import native
from victoriametrics_tpu.utils import costacc, flightrec
from victoriametrics_tpu.utils import metrics as metricslib

FAMILY = "vm_query_phase_seconds_total{"
WALL = "vm_query_wall_seconds_total"


def _metrics() -> dict:
    out = {}
    for line in metricslib.REGISTRY.write_prometheus().splitlines():
        if line.startswith((FAMILY, WALL, "vm_fetch_phase_seconds_total{")):
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


def _delta(m0: dict, m1: dict, prefix: str) -> dict:
    return {k: v - m0.get(k, 0.0) for k, v in m1.items()
            if k.startswith(prefix)}


def _member(phase: str) -> str:
    return f'{FAMILY}phase="{phase}"}}'


def test_family_is_complete_at_import():
    """Every member is exported from import on (0 where nothing ran): a
    reader never has to tell "absent" from "idle"."""
    m = _metrics()
    for name in flightrec.QUERY_PHASES:
        assert _member(name) in m, name
    assert WALL in m


def test_self_times_partition_the_root_wall(monkeypatch):
    """(a) nested and sibling phases, a lap chain of a family of its own
    and a child on a pool worker: the query family's deltas sum to the
    root's wall; the worker's phase reaches the ring (with the request's
    ctx) and its own family, never the query family."""
    from victoriametrics_tpu.utils import workpool
    monkeypatch.setenv("VM_SEARCH_WORKERS", "2")
    own = metricslib.REGISTRY.float_counter(
        'vm_fetch_phase_seconds_total{phase="decode"}')
    started, release = threading.Event(), threading.Event()
    info = {}

    def on_worker():
        started.set()
        release.wait(10)
        info["tid"] = threading.get_ident()
        with flightrec.phase("fetch:decode", counter=own):
            time.sleep(0.002)
        with flightrec.phase("device:execute"):  # no root on this thread
            time.sleep(0.002)

    tr = costacc.CostTracker()
    m0 = _metrics()
    with flightrec.phase("serve:other", root=True) as root:
        ctx = flightrec.get_ctx()
        prev = costacc.set_current(tr)
        try:
            with flightrec.phase("eval:other"):
                time.sleep(0.003)
                with flightrec.phase("fetch:wait"):
                    fut = workpool.POOL.submit(on_worker)
                    assert started.wait(10)  # so it runs on a pool thread
                    release.set()
                    fut.result()
                    # an inline stage chain of the fetch family
                    with flightrec.phase("fetch:decode", counter=own) as ph:
                        time.sleep(0.002)
                        ph.lap("fetch:decode", own)
                        time.sleep(0.001)
                with flightrec.phase("device:tile_build") as build:
                    with flightrec.phase("device:upload"):
                        time.sleep(0.002)
                    time.sleep(0.001)
            with flightrec.phase("serve:rows"):
                time.sleep(0.002)
            with flightrec.phase("serve:json"):
                time.sleep(0.001)
        finally:
            costacc.set_current(prev)
        time.sleep(0.002)
    m1 = _metrics()
    assert ctx != 0 and flightrec.get_ctx() == 0
    assert info["tid"] != threading.get_ident()
    fam = _delta(m0, m1, FAMILY)
    wall = m1[WALL] - m0[WALL]
    assert wall == pytest.approx(root.dur, abs=1e-9)
    assert sum(fam.values()) == pytest.approx(wall, abs=1e-3)
    ticked = {k for k, v in fam.items() if v > 0}
    assert ticked == {_member(n) for n in (
        "serve:other", "eval:other", "fetch:wait", "device:tile_build",
        "device:upload", "serve:rows", "serve:json")}
    # the worker's device:execute did not charge the family; the fetch
    # stages charged their own (worker 1 + inline 2 laps), and the
    # inline ones read as fetch:wait in the query family
    assert fam[_member("device:execute")] == 0
    own_d = _delta(m0, m1, "vm_fetch_phase_seconds_total{")
    assert own_d['vm_fetch_phase_seconds_total{phase="decode"}'] >= 0.005
    assert fam[_member("fetch:wait")] >= 0.003
    # the ring: the worker's phases carry the request's ctx
    evs = flightrec.ctx_events(ctx)
    on_w = {name for _t0, _dur, name, tid in evs if tid == info["tid"]}
    assert {"fetch:decode", "device:execute"} <= on_w
    # the cost tracker got SELF times: one thread's buckets never overlap
    assert tr.wall_ms["device:tile_build"] + tr.wall_ms["device:upload"] \
        == pytest.approx(build.dur * 1e3, abs=1e-6)
    assert tr.wall_ms["eval:other"] >= 3.0
    split = flightrec.phase_split(ctx)
    assert split["device:tile_build"] == pytest.approx(
        tr.wall_ms["device:tile_build"] / 1e3, abs=1e-6)


@pytest.mark.parametrize("between", [False, True],
                         ids=["direct_child", "through_a_plain_phase"])
def test_a_stage_carved_out_of_a_stage_keeps_the_familys_sum(between):
    """A phase with a counter nested in one (`fetch:pending_convert` in
    the collect stage): each member gets its own share, the family sums
    to the outer stage's duration, the query family reads none of it."""
    outer = metricslib.REGISTRY.float_counter(
        'vm_fetch_phase_seconds_total{phase="assemble_native"}')
    inner = metricslib.REGISTRY.float_counter(
        'vm_fetch_phase_seconds_total{phase="pending_convert"}')
    m0 = _metrics()
    with flightrec.phase("serve:other", root=True):
        with flightrec.phase("fetch:wait"):
            with flightrec.phase("fetch:index_search", counter=outer) as ph:
                ph.lap("fetch:assemble_native", outer)
                time.sleep(0.002)
                if between:
                    with flightrec.phase("device:upload"), \
                            flightrec.phase("fetch:pending_convert",
                                            counter=inner) as conv:
                        time.sleep(0.004)
                else:
                    with flightrec.phase("fetch:pending_convert",
                                         counter=inner) as conv:
                        time.sleep(0.004)
                t_lap = time.perf_counter()
                stage = t_lap - ph.t0
                ph.lap("fetch:assemble", outer)
    m1 = _metrics()
    d = _delta(m0, m1, "vm_fetch_phase_seconds_total{")
    got_inner = d['vm_fetch_phase_seconds_total{phase="pending_convert"}']
    got_outer = d['vm_fetch_phase_seconds_total{phase="assemble_native"}']
    assert got_inner == pytest.approx(conv.dur, abs=1e-9)
    assert got_inner >= 0.004 and got_outer >= 0.002
    assert got_inner + got_outer == pytest.approx(stage, abs=2e-4)
    fam = _delta(m0, m1, FAMILY)
    assert fam[_member("fetch:wait")] >= (0.002 if between else 0.006)


def test_abandoned_child_does_not_outlive_its_parent():
    """A phase left open (a generator dropped mid-phase) is swept off
    the stack when its parent exits; the next request starts clean."""
    with flightrec.phase("serve:other", root=True):
        flightrec.phase("eval:other").__enter__()   # never exited
    m0 = _metrics()
    with flightrec.phase("serve:other", root=True) as root:
        with flightrec.phase("serve:rows"):
            pass
    m1 = _metrics()
    assert sum(_delta(m0, m1, FAMILY).values()) == pytest.approx(
        root.dur, abs=1e-6)


def test_recorder_off_keeps_the_counters_and_drops_ring_and_annotation(
        monkeypatch):
    """VM_FLIGHTREC=0 turns off the flight RECORDER (ring events, host
    plane annotations); the cost plane and the counters go on."""
    opened = []

    class Ann:
        def __init__(self, label):
            opened.append(label)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(flightrec, "_annotator", Ann)
    monkeypatch.setenv("VM_FLIGHTREC", "0")
    flightrec.reconfigure()
    tr = costacc.CostTracker()
    prev = costacc.set_current(tr)
    try:
        m0 = _metrics()
        with flightrec.phase("serve:other", root=True) as root:
            ctx = flightrec.get_ctx()
            with flightrec.phase("serve:rows"):
                pass
        m1 = _metrics()
    finally:
        costacc.set_current(prev)
        monkeypatch.delenv("VM_FLIGHTREC")
        flightrec.reconfigure()
    assert opened == [] and flightrec.ctx_events(ctx) == []
    assert sum(_delta(m0, m1, FAMILY).values()) == pytest.approx(
        root.dur, abs=1e-6)
    assert set(tr.wall_ms) == {"serve:other", "serve:rows"}
    with flightrec.phase("serve:rows"):     # back on: annotated again
        pass
    assert opened == ["vm:serve:rows"]


# -- one device-backed query_range over HTTP ---------------------------------

NS, NN, STEP = 128, 240, 60_000


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """PrometheusAPI + HTTPServer over a device engine at a test's size:
    (client, ingest(n_cols) -> end_ms, query)."""
    if not native.available():
        pytest.skip("needs native lib")
    from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
    from victoriametrics_tpu.httpapi.server import HTTPServer
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    from victoriametrics_tpu.storage.storage import Storage
    # 12 h back, like the benchmark's bulk: every refresh is a full eval
    # on the resident window (no result-cache tail merge)
    t0 = (int(time.time() * 1000) - 12 * 3_600_000) // STEP * STEP
    rng = np.random.default_rng(7)
    s = Storage(str(tmp_path_factory.mktemp("phase") / "s"))
    keys = [f'ps{{idx="{i}",instance="h-{i % 8}"}}'.encode()
            for i in range(NS)]
    keybuf = b"".join(keys)
    klens = np.fromiter((len(k) for k in keys), np.int64, NS)
    koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
    state = {"n": 0, "last": np.zeros(NS)}

    def ingest(n_cols: int) -> int:
        ts = (t0 + (state["n"] + np.arange(n_cols, dtype=np.int64))[None, :]
              * 15_000 + rng.integers(0, 2_000, (NS, n_cols)))
        vals = state["last"][:, None] + np.cumsum(
            rng.integers(1, 50, (NS, n_cols)), axis=1)
        state["last"] = vals[:, -1].astype(np.float64)
        state["n"] += n_cols
        s.add_rows_columnar(native.ColumnarRows(
            keybuf, np.repeat(koffs, n_cols), np.repeat(klens, n_cols),
            ts.reshape(-1), vals.reshape(-1).astype(np.float64)))
        return t0 + -(-(state["n"] * 15_000) // STEP) * STEP

    end = ingest(NN)
    s.force_flush()
    api = PrometheusAPI(s, tpu_engine=TPUEngine(value_dtype=np.float32,
                                                min_series=2))
    srv = HTTPServer("127.0.0.1", 0)
    api.register(srv)
    srv.start()
    c = Client(srv.port)
    q = "sum by (instance)(rate(ps[5m]))"
    span = 40 * STEP

    def refresh(end_ms: int) -> dict:
        # the root closes after the last byte is written, a moment
        # after the client has read it: return once it has closed, so
        # every request's counters have landed before the next snapshot
        wall0 = _metrics()[WALL]
        res = c.query_range(q, (end_ms - span) / 1e3, end_ms / 1e3,
                            STEP // 1000)
        for _ in range(2000):
            if _metrics()[WALL] > wall0:
                return res
            time.sleep(0.005)
        raise AssertionError("the request's root never closed")

    # cold adoption, then one advance: the window is resident afterwards
    refresh(end)
    refresh(ingest(4))
    try:
        yield refresh, ingest
    finally:
        srv.stop()
        s.close()


def test_served_query_range_partitions_its_wall(served):
    """(b) the deltas of every family member sum to the delta of
    vm_query_wall_seconds_total; rows, json, send and the (sub-MiB)
    upload each tick."""
    refresh, ingest = served
    end = ingest(4)
    m0 = _metrics()
    res = refresh(end)
    m1 = _metrics()
    assert res["status"] == "success" and len(res["data"]["result"]) == 8
    fam = _delta(m0, m1, FAMILY)
    wall = m1[WALL] - m0[WALL]
    assert wall > 0
    assert sum(fam.values()) == pytest.approx(wall, rel=0.01)
    for name in ("serve:rows", "serve:json", "serve:send", "serve:other",
                 "eval:other", "fetch:wait", "device:tile_build",
                 "device:upload", "device:execute", "device:download",
                 "cache:put"):
        assert fam[_member(name)] > 0, name
    # an inline fetch charges its own family and reads as fetch:wait here
    assert sum(_delta(m0, m1, "vm_fetch_phase_seconds_total{").values()) > 0
    assert not any('phase="fetch:index_search"' in k for k in fam)


def test_phases_on_the_profilers_host_plane(served, tmp_path):
    """(c) under a profiler session the phases are vm:<name> events on
    the host plane, on the profiler's own clock, inside the interval of
    an annotation the caller opened round the call."""
    import jax
    from jax.profiler import ProfileData
    refresh, ingest = served
    end = ingest(4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test:query_range"):
            refresh(end)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(("vm:", "test:"))]
    (_, lo, hi), = [e for e in events if e[0] == "test:query_range"]
    names = {e[0] for e in events}
    for want in ("vm:serve:other", "vm:serve:rows", "vm:serve:json",
                 "vm:serve:send", "vm:eval:other", "vm:device:execute"):
        assert want in names, (want, sorted(names))
        for name, start, stop in events:
            if name == want:
                assert lo <= start and stop <= hi, name


def test_no_jax_no_annotation():
    """(d) a process that never imported jax records phases, charges the
    counters and emits no annotation."""
    code = """
import sys
from victoriametrics_tpu.utils import flightrec, metrics
with flightrec.phase("serve:other", root=True) as root:
    with flightrec.phase("serve:rows"):
        pass
assert "jax" not in sys.modules, "flightrec pulled jax in"
assert flightrec._annotator is None
ring = flightrec._tls.ring
names = [e[2] for e in ring.snapshot(0.0)]
depths = [e[6] for e in ring.snapshot(0.0)]
assert names == ["serve:rows", "serve:other"], names
assert depths == [2, 1], depths
text = metrics.REGISTRY.write_prometheus()
assert 'vm_query_phase_seconds_total{phase="serve:rows"}' in text
assert root.dur > 0
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_the_writers_counters_read_0_on_a_server_that_answered_nothing():
    """(e) the matrix writer's families (points by writer, the parallel
    points, the metric memo's hits and misses) are on /metrics at 0 from
    the server's start: the benchmark's ratios read 0, never "absent"."""
    code = """
import tempfile, urllib.request
from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
from victoriametrics_tpu.httpapi.server import HTTPServer
from victoriametrics_tpu.storage.storage import Storage
with tempfile.TemporaryDirectory() as d:
    s = Storage(d)
    srv = HTTPServer("127.0.0.1", 0)
    PrometheusAPI(s).register(srv)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
            text = r.read().decode()
    finally:
        srv.stop()
        s.close()
lines = dict(l.rsplit(" ", 1) for l in text.splitlines()
             if l.startswith("vm_http_matrix_"))
assert lines == {
    'vm_http_matrix_points_total{writer="native"}': "0",
    'vm_http_matrix_points_total{writer="python"}': "0",
    "vm_http_matrix_parallel_points_total": "0",
    'vm_http_matrix_metric_memo_total{result="hit"}': "0",
    'vm_http_matrix_metric_memo_total{result="miss"}': "0"}, lines
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
