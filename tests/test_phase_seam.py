"""The phase seam (utils/flightrec.phase): self times partition a
request root's wall, one exit feeds the ring, the cost tracker, the
query-phase counter family, its CPU twin and the profiler's host plane;
background regions keep their busy-clocks, which a root reads at its
edges.  Structural assertions, and where a clock is read, against known
sleeps and spins with tolerances of milliseconds."""

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
CPU = "vm_query_phase_cpu_seconds_total{"
FETCH = "vm_fetch_phase_seconds_total{"
FETCH_CPU = "vm_fetch_phase_cpu_seconds_total{"
OVERLAP = "vm_query_background_overlap_seconds_total{"


def _metrics() -> dict:
    out = {}
    for line in metricslib.REGISTRY.write_prometheus().splitlines():
        if line.startswith((FAMILY, WALL, FETCH, CPU, FETCH_CPU, OVERLAP)):
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


def _spin(seconds: float) -> None:
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def _twin(name: str) -> str:
    """The CPU twin of a wall member's series name."""
    return name.replace("_seconds_total{", "_cpu_seconds_total{", 1)


@pytest.mark.parametrize("family", ["query", "fetch"])
@pytest.mark.parametrize("work", ["sleep", "spin"])
def test_the_cpu_twin_reads_the_threads_cpu(family, work):
    """A phase that sleeps reads under a tenth of its wall in the CPU
    twin, one that spins over four fifths: in the query family and in a
    family of the phase's own.  (Best of three tries: a spinner on a
    loaded box waits for a core, and that is off-CPU time too.)"""
    decode = metricslib.REGISTRY.float_counter(f'{FETCH}phase="decode"}}')
    member = _member("eval:other") if family == "query" \
        else f'{FETCH}phase="decode"}}'
    do = time.sleep if work == "sleep" else _spin
    shares = []
    for _ in range(3):
        m0 = _metrics()
        with flightrec.phase("serve:other", root=True):
            if family == "query":
                with flightrec.phase("eval:other"):
                    do(0.05)
            else:
                with flightrec.phase("fetch:wait"), \
                        flightrec.phase("fetch:decode", counter=decode):
                    do(0.05)
        m1 = _metrics()
        wall = m1[member] - m0[member]
        cpu = m1[_twin(member)] - m0[_twin(member)]
        assert wall >= 0.05 and 0 <= cpu <= wall
        shares.append(cpu / wall)
        if (shares[-1] < 0.1) if work == "sleep" else (shares[-1] > 0.8):
            break
    assert (min(shares) < 0.1) if work == "sleep" else (max(shares) > 0.8), \
        shares


@pytest.mark.parametrize("between", [False, True],
                         ids=["direct_child", "through_a_plain_phase"])
def test_a_carved_stage_carves_its_cpu_as_its_wall(between):
    """A stage with a counter nested in another carves its thread-CPU
    out of the outer stage the way it carves its wall: each CPU twin
    reads the CPU of exactly the stretch its wall member covers, and in
    both twins every member reads no more CPU than wall."""
    outer = metricslib.REGISTRY.float_counter(
        f'{FETCH}phase="assemble_native"}}')
    inner = metricslib.REGISTRY.float_counter(
        f'{FETCH}phase="pending_convert"}}')
    m0 = _metrics()
    with flightrec.phase("serve:other", root=True) as root:
        with flightrec.phase("fetch:wait"):
            with flightrec.phase("fetch:index_search", counter=outer) as ph:
                ph.lap("fetch:assemble_native", outer)
                c_stage = time.thread_time()
                _spin(0.01)
                time.sleep(0.005)
                if between:
                    with flightrec.phase("device:upload"), \
                            flightrec.phase("fetch:pending_convert",
                                            counter=inner):
                        c0 = time.thread_time()
                        _spin(0.02)
                        c_inner = time.thread_time() - c0
                else:
                    with flightrec.phase("fetch:pending_convert",
                                         counter=inner):
                        c0 = time.thread_time()
                        _spin(0.02)
                        c_inner = time.thread_time() - c0
                c_whole = time.thread_time() - c_stage
                ph.lap("fetch:assemble", outer)
    m1 = _metrics()
    d = {k: m1[k] - m0.get(k, 0.0) for k in m1}
    got_inner = d[f'{FETCH_CPU}phase="pending_convert"}}']
    got_outer = d[f'{FETCH_CPU}phase="assemble_native"}}']
    # the seam's own entry and exit are a few microseconds of CPU
    assert got_inner == pytest.approx(c_inner, abs=5e-4)
    assert got_inner + got_outer == pytest.approx(c_whole, abs=5e-4)
    assert got_outer < got_inner
    for name, wall in d.items():
        if name.startswith((FAMILY, FETCH)):
            assert d[_twin(name)] <= wall + 1e-9, name
    cpu_sum = sum(v for k, v in d.items() if k.startswith(CPU))
    assert 0 < cpu_sum <= d[WALL] + 1e-9
    assert d[WALL] == pytest.approx(root.dur, abs=1e-9)
    if between:
        # the query family keeps the carved stage's time where its wall is
        assert d[_twin(_member("device:upload"))] >= got_inner - 5e-4


def test_the_new_families_read_0_from_import():
    """Every member of the CPU twins and of the background overlap is on
    the exposition at 0 in a process that has run nothing but the imports
    of the modules that own the fetch's stages: each fetch member comes
    with its twin (metricslib.fetch_phase)."""
    code = """
from victoriametrics_tpu.utils import flightrec, metrics
import victoriametrics_tpu.storage.storage, victoriametrics_tpu.query.eval
text = metrics.REGISTRY.write_prometheus()
lines = dict(l.rsplit(" ", 1) for l in text.splitlines()
             if l and not l.startswith("#"))
fetch = [n.split('"')[1] for n in lines
         if n.startswith("vm_fetch_phase_seconds_total{")]
assert sorted(fetch) == sorted(("index_search", "collect", "decode",
    "assemble", "assemble_native", "queue_wait", "pending_convert",
    "rollup")), fetch
want = [f'vm_query_phase_cpu_seconds_total{{phase="{n}"}}'
        for n in flightrec.QUERY_PHASES]
want += [f'vm_fetch_phase_cpu_seconds_total{{phase="{n}"}}' for n in fetch]
want += [f'vm_query_background_overlap_seconds_total{{kind="{k}"}}'
         for k in ("flush", "merge", "convert", "gc")]
assert len(want) == 18 + 8 + 4
for name in want:
    assert float(lines[name]) == 0.0, name
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def _overlap(m0: dict, m1: dict) -> dict:
    return {k: m1[f'{OVERLAP}kind="{k}"}}'] - m0[f'{OVERLAP}kind="{k}"}}']
            for k in flightrec.BACKGROUND_KINDS}


@pytest.mark.parametrize("region,kind", [
    ("flush:part", "flush"), ("merge:index", "merge"),
    ("ingest:convert", "convert")])
@pytest.mark.parametrize("starts", ["inside", "before", "around"])
def test_a_background_region_counts_its_stretch_of_the_root(region, kind,
                                                            starts):
    """A region of a background kind on another thread that overlaps a
    request root for a known stretch reads that stretch (to 10 ms) in
    its kind, whether it opened inside the root, before it, or before it
    and closed after it."""
    seen = {}
    opened, release = threading.Event(), threading.Event()

    def background():
        with flightrec.phase(region) as ph:
            seen["ph"] = ph
            opened.set()
            release.wait(10)
            time.sleep(0.06)

    t = threading.Thread(target=background)
    m0 = _metrics()
    if starts != "inside":
        t.start()
        assert opened.wait(10)
        time.sleep(0.03)
    with flightrec.phase("serve:other", root=True) as root:
        time.sleep(0.02)
        if starts == "inside":
            t.start()
            assert opened.wait(10)
        if starts != "around":
            release.set()
            t.join(10)
        time.sleep(0.03)
    release.set()
    t.join(10)
    m1 = _metrics()
    ph = seen["ph"]
    want = min(ph.t0 + ph.dur, root.t0 + root.dur) - max(ph.t0, root.t0)
    got = _overlap(m0, m1)
    assert want > 0.05
    assert got[kind] == pytest.approx(want, abs=0.01)
    assert got[kind] <= root.dur


def test_a_region_the_serving_thread_runs_itself_is_not_background():
    """A conversion that the thread holding the request root runs while it
    waits (a pool-helping wait inside ``fetch:pending_convert``) is the
    request's own work: it keeps its ingest counter and ring event but
    leaves the busy-clock alone, so the root's overlap reads 0."""
    ing = metricslib.ingest_phase("convert")
    i = flightrec.BACKGROUND_KINDS.index("convert")
    before = flightrec._busy[i]
    c0 = ing.get()
    m0 = _metrics()
    with flightrec.phase("serve:other", root=True):
        with flightrec.phase("fetch:pending_convert",
                             counter=metricslib.fetch_phase(
                                 "pending_convert")):
            with flightrec.phase("ingest:convert", counter=ing) as ph:
                assert flightrec._busy[i] is before
                time.sleep(0.03)
    m1 = _metrics()
    assert flightrec._busy[i] is before
    assert ing.get() - c0 == pytest.approx(ph.dur)
    assert ph.dur >= 0.03
    assert _overlap(m0, m1)["convert"] == 0.0
    d = _delta(m0, m1, FETCH)
    assert d[f'{FETCH}phase="pending_convert"}}'] >= 0.03


def test_overlapping_regions_of_a_kind_count_once_and_outside_ones_not():
    """Two merges that overlap each other inside a root count their
    union; a merge that ended before the root counts nothing."""
    spans = []

    def merge(delay, hold):
        time.sleep(delay)
        with flightrec.phase("merge:part") as ph:
            time.sleep(hold)
        spans.append((ph.t0, ph.t0 + ph.dur))

    with flightrec.phase("merge:table") as before:
        time.sleep(0.02)
    m0 = _metrics()
    with flightrec.phase("serve:other", root=True):
        time.sleep(0.01)
        ts = [threading.Thread(target=merge, args=a)
              for a in ((0.0, 0.06), (0.03, 0.06))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(10)
        time.sleep(0.01)
    m1 = _metrics()
    assert before.dur >= 0.02
    union = max(e for _s, e in spans) - min(s for s, _e in spans)
    got = _overlap(m0, m1)["merge"]
    assert union < sum(e - s for s, e in spans) - 0.02
    assert got == pytest.approx(union, abs=0.01)
    m0 = _metrics()
    with flightrec.phase("serve:other", root=True):
        time.sleep(0.02)
    assert _overlap(m0, _metrics())["merge"] == pytest.approx(0.0, abs=1e-3)


def test_a_busy_clock_loses_no_edge_under_many_threads():
    """Sixteen threads open and close conversions as fast as they can
    under a short switch interval: every region's edges land, so the
    clock ends idle, and its busy seconds cover the union of the regions
    and no more than the whole stress (a lost update would leave it open
    or lose a stretch)."""
    i = flightrec.BACKGROUND_KINDS.index("convert")
    spans = []

    def convert():
        for k in range(1000):
            with flightrec.phase("ingest:convert") as ph:
                if k % 10 == 0:
                    _spin(1e-4)
            spans.append((ph.t0, ph.t0 + ph.dur))

    acc0 = flightrec._busy[i][2]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t_start = time.perf_counter()
    try:
        ts = [threading.Thread(target=convert) for _ in range(16)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    stress = time.perf_counter() - t_start
    assert not any(t.is_alive() for t in ts)
    assert len(spans) == 16_000
    n, _since, acc = flightrec._busy[i]
    assert n == 0
    union, hi = 0.0, None
    for s0, s1 in sorted(spans):
        if hi is None or s0 > hi:
            union += s1 - s0
            hi = s1
        elif s1 > hi:
            union += s1 - hi
            hi = s1
    # each edge is taken under the clock's lock, after the wait for it:
    # the clock's stretches hold the regions' own
    assert union - 1e-6 <= acc - acc0 <= stress


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
    # a collection may open its own mark whenever the recorder is on
    opened[:] = [o for o in opened if o != "vm:gc"]
    assert opened == [] and flightrec.ctx_events(ctx) == []
    assert sum(_delta(m0, m1, FAMILY).values()) == pytest.approx(
        root.dur, abs=1e-6)
    assert set(tr.wall_ms) == {"serve:other", "serve:rows"}
    with flightrec.phase("serve:rows"):     # back on: annotated again
        pass
    assert [o for o in opened if o != "vm:gc"] == ["vm:serve:rows"]


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


def _xtrace():
    """The benchmark's trace reader, by its path."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "seam_xtrace", os.path.join(REPO, "benchmark", "xtrace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_and_merge(tmp_path, monkeypatch):
    """A partition that spills to the pool, flushes twice and merges, and
    an index table that does the same: every background region once."""
    from victoriametrics_tpu.storage import mergeset, partition
    from victoriametrics_tpu.storage.tsid import TSID
    monkeypatch.setenv("VM_INGEST_SHARDS", "2")
    monkeypatch.setenv("VM_SEARCH_WORKERS", "2")
    monkeypatch.setattr(partition, "MAX_PENDING_ROWS", 40)
    part = partition.Partition(str(tmp_path / "p"), "2025_07")
    mtab = mergeset.Table(str(tmp_path / "m"))
    t0 = 1_753_700_000_000
    try:
        for k in range(2):
            part.add_rows([(TSID(metric_group_id=1, metric_id=i),
                            t0 + k * 60_000 + i, float(i)) for i in range(50)])
            part.flush_to_disk()
            mtab.add_items([b"k%03d" % (10 * k + i) for i in range(10)])
            mtab.flush_to_disk()
        part.force_merge()
        mtab.force_merge()
    finally:
        part.close()
        mtab.close()


def test_the_ingest_family_reads_the_part_writes(tmp_path, monkeypatch):
    """vm_ingest_phase_seconds_total{phase="flush"|"merge"} read what
    they read when the regions were timed by hand: the seconds of the
    part writes inside the MERGE_GATE, which the flush and merge duration
    histograms sum too (data parts and index parts alike); the pool's
    spill conversion is a member of its own."""
    reg = metricslib.REGISTRY
    hists = {"flush": [reg.histogram(f'vm_storage_flush_duration_seconds'
                                     f'{{type="{t}"}}')
                       for t in ("storage/small", "indexdb/mergeset")],
             "merge": [reg.histogram(f'vm_storage_merge_duration_seconds'
                                     f'{{type="{t}"}}')
                       for t in ("storage/file", "indexdb/mergeset")]}
    ing = {ph: metricslib.ingest_phase(ph)
           for ph in ("flush", "merge", "convert")}
    h0 = {k: sum(h.get_sum() for h in v) for k, v in hists.items()}
    i0 = {k: c.get() for k, c in ing.items()}
    _write_and_merge(tmp_path, monkeypatch)
    for k in ("flush", "merge"):
        got = ing[k].get() - i0[k]
        assert got > 0
        assert got == pytest.approx(
            sum(h.get_sum() for h in hists[k]) - h0[k], abs=1e-9), k
    assert ing["convert"].get() > i0["convert"]


def test_background_work_is_marked_on_the_host_plane(tmp_path, monkeypatch):
    """(f) the flushes, merges, the pool's spill conversion and gc pauses
    are vm: marks on the profiler's host plane, in the lists the
    benchmark's trace reader makes of it (what its idle gaps are charged
    to)."""
    import gc

    import jax
    monkeypatch.setattr(flightrec, "_annotator", jax.profiler.TraceAnnotation)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        _write_and_merge(tmp_path, monkeypatch)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    trace = _xtrace().read(str(tmp_path / "trace"))
    names = {e[0] for plane in trace["planes"] for line in plane["lines"]
             for e in line["events"]}
    assert {"vm:flush:part", "vm:merge:part", "vm:flush:index",
            "vm:merge:index", "vm:ingest:convert", "vm:gc"} <= names, \
        sorted(names)


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
