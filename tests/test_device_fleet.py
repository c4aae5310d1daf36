"""Tier-1 + device-suite guards for fleet-batched device serving
(ISSUE 19): every active materialized stream with a device-resident
window is served from ONE fused mesh launch per bucket per interval
(query/fleet.py), not one program per stream.

Guards:
  * exactly one fused launch per bucket per warm interval, zero
    recompiles (plane compile counter reads REAL backend compiles via
    the jax monitoring event, not jit-cache growth);
  * numeric parity at rtol=1e-12 with BOTH oracles — the cold polled
    host evaluation and the VM_DEVICE_FLEET=0 per-stream rolling path —
    across mixed grids landing in different buckets;
  * churn (new same-shaped subscriber, structural version bump) repacks
    members without recompiling the bucket and recovers parity;
  * the rows-share cost split of the shared launch sums exactly to the
    launch wall across /api/v1/status/usage rows;
  * a race-marked stress (tools/race.sh): subscriber churn + live
    ingest + concurrent pumps while the fleet serves.

Values are compared NUMERICALLY (not as formatted strings): mesh-device
and host summation orders differ at the last ulp, which is documented
drift, not a regression."""

import json
import threading
import time

import numpy as np
import pytest

from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
from victoriametrics_tpu.query import fleet as fleetmod
from victoriametrics_tpu.query import rollup_result_cache as rrc
from victoriametrics_tpu.query.exec import exec_query
from victoriametrics_tpu.query.matstream import StreamClient
from victoriametrics_tpu.query.types import EvalConfig
from victoriametrics_tpu.storage.storage import Storage

STEP = 60_000
SCRAPE = 15_000
NS = 16
NN = 240
DUR = 20 * STEP
PANELS = [
    "sum by (g)(rate(fl_m[5m]))",   # G=4  -> rung 8   (bucket A)
    "sum by (i)(rate(fl_m[5m]))",   # G=16 -> rung 16  (bucket B)
    "max by (g)(rate(fl_m[5m]))",   # bucket A (aggr code is traced)
    "count by (g)(rate(fl_m[5m]))",  # bucket A
]


def _mesh8():
    import jax

    from victoriametrics_tpu.parallel.mesh import make_mesh
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(devs[:8])


def _seed(s: Storage, t0: int, ns: int = NS, n: int = NN, seed: int = 7):
    rng = np.random.default_rng(seed)
    rows = []
    last = np.empty(ns)
    for i in range(ns):
        vals = np.cumsum(rng.integers(0, 30, n)).astype(np.float64)
        last[i] = vals[-1]
        rows.extend((({"__name__": "fl_m", "i": str(i), "g": f"g{i % 4}"},
                      t0 + j * SCRAPE, float(vals[j])) for j in range(n)))
    s.add_rows(rows)
    s.force_flush()
    return last, rng


def _ingest(s: Storage, rng, last, end: int, ns: int = NS, k: int = 4):
    rows = []
    for i in range(ns):
        incr = np.cumsum(rng.integers(0, 30, k))
        rows.extend((({"__name__": "fl_m", "i": str(i), "g": f"g{i % 4}"},
                      end - STEP + (j + 1) * SCRAPE, float(last[i] + incr[j]))
                     for j in range(k)))
        last[i] += incr[-1]
    s.add_rows(rows)


def _grid_t0(n: int = NN) -> int:
    now = int(time.time() * 1000)
    return (now - (n - 1) * SCRAPE) // STEP * STEP


def _end0(t0: int, n: int = NN) -> int:
    return t0 + ((n - 1) * SCRAPE // STEP + 1) * STEP


def polled(storage, q, start, end, step):
    """The host-path cold oracle (no tpu engine, no caches)."""
    ec = EvalConfig(start=start, end=end, step=step, storage=storage,
                    disable_cache=True)
    rows = exec_query(ec, q)
    grid = ec.timestamps() / 1e3
    out = {}
    for r in rows:
        vals = np.array([[float(t), v] for t, v in zip(grid, r.values)
                         if not np.isnan(v)])
        if len(vals):
            out[json.dumps(r.metric_name.to_dict(), sort_keys=True)] = vals
    return out


def _np_rows(entries):
    return {json.dumps(e["metric"], sort_keys=True):
            np.array([[float(t), float(v)] for t, v in e["values"]])
            for e in entries}


def _assert_close(got: dict, want: dict, ctx: str = ""):
    assert set(got) == set(want), (
        ctx, sorted(set(got) ^ set(want))[:4])
    for k in sorted(got):
        assert got[k].shape == want[k].shape, (ctx, k)
        np.testing.assert_allclose(got[k], want[k], rtol=1e-12, atol=0,
                                   err_msg=f"{ctx} {k}")


def _pump(subs, clis, end):
    for sub, cli in zip(subs, clis):
        f = sub.next_frame(timeout_s=10.0, now_ms=end)
        assert f is not None, "stream did not advance"
        cli.apply(f)


def test_fleet_single_launch_per_interval(tmp_path):
    """THE fleet guard (tools/check.sh device stage): N panels of mixed
    aggregates over shared buckets cost exactly one fused launch per
    bucket per warm interval, recompile nothing, and stay at rtol=1e-12
    parity with the cold host oracle."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    mesh = _mesh8()
    rrc.GLOBAL.reset()
    s = Storage(str(tmp_path / "s"))
    try:
        t0 = _grid_t0()
        last, rng = _seed(s, t0)
        end = _end0(t0)
        engine = TPUEngine(min_series=4, mesh=mesh)
        api = PrometheusAPI(s, engine)
        subs = [api.matstreams.subscribe(q, STEP, DUR) for q in PANELS]
        clis = [StreamClient() for _ in PANELS]
        for sub, cli in zip(subs, clis):
            f = sub.next_frame(timeout_s=10.0, now_ms=end)
            assert f["type"] == "snapshot"
            cli.apply(f)
        plane = engine.fleet()
        for r in range(1, 5):
            end += STEP
            _ingest(s, rng, last, end)
            st0 = plane.stats()
            _pump(subs, clis, end)
            st1 = plane.stats()
            for q, cli in zip(PANELS, clis):
                _assert_close(_np_rows(cli.result()),
                              polled(s, q, end - DUR, end, STEP),
                              ctx=f"interval {r} {q!r}")
            if r >= 2:
                nb = st1["buckets"]
                assert nb == 2, st1
                assert st1["members"] == len(PANELS), st1
                assert st1["launches"] - st0["launches"] == nb, (
                    f"interval {r}: {st1['launches'] - st0['launches']} "
                    f"launches for {nb} buckets — fleet batching regressed "
                    "to per-stream programs")
                assert st1["served"] - st0["served"] == len(PANELS), st1
                assert st1["compiles"] - st0["compiles"] == 0, (
                    f"interval {r}: warm interval paid a backend compile")
    finally:
        s.close()


def _run_sequence(tmp_path, sub, mesh, t0, panels, intervals=4):
    """One deterministic rolling sequence (same t0 + seeds => identical
    rows); returns per-interval {query: rows-map}."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    rrc.GLOBAL.reset()
    s = Storage(str(tmp_path / sub))
    try:
        last, rng = _seed(s, t0)
        end = _end0(t0)
        engine = TPUEngine(min_series=4, mesh=mesh)
        api = PrometheusAPI(s, engine)
        subs = [api.matstreams.subscribe(q, STEP, d) for q, d in panels]
        clis = [StreamClient() for _ in panels]
        for sub_, cli in zip(subs, clis):
            cli.apply(sub_.next_frame(timeout_s=10.0, now_ms=end))
        out = []
        for _ in range(intervals):
            end += STEP
            _ingest(s, rng, last, end)
            _pump(subs, clis, end)
            out.append({q: _np_rows(cli.result())
                        for (q, _), cli in zip(panels, clis)})
        return out, engine.fleet().stats()
    finally:
        s.close()


def test_fleet_matches_per_stream_oracle_mixed_grids(tmp_path, monkeypatch):
    """Batched-vs-per-stream equality oracle: the same deterministic
    sequence served by the fleet and by VM_DEVICE_FLEET=0 (the
    per-stream rolling path) agrees at rtol=1e-12 — across two panels
    with DIFFERENT durations (different T rungs => different buckets)."""
    mesh = _mesh8()
    panels = [("sum by (g)(rate(fl_m[5m]))", DUR),
              ("max by (i)(rate(fl_m[5m]))", 30 * STEP)]
    t0 = _grid_t0()
    monkeypatch.delenv("VM_DEVICE_FLEET", raising=False)
    got, st = _run_sequence(tmp_path, "fleet-on", mesh, t0, panels)
    assert st["launches"] > 0 and st["members"] == 2, (
        f"fleet never engaged: {st}")
    monkeypatch.setenv("VM_DEVICE_FLEET", "0")
    want, st_off = _run_sequence(tmp_path, "fleet-off", mesh, t0, panels)
    assert st_off["launches"] == 0, (
        "VM_DEVICE_FLEET=0 still launched fleet programs")
    for r, (g, w) in enumerate(zip(got, want)):
        for q, _ in panels:
            _assert_close(g[q], w[q], ctx=f"interval {r} {q!r}")


def test_fleet_churn_repacks_without_recompiling(tmp_path):
    """Member churn within a bucket's ladder rungs never recompiles: a
    new same-shaped subscriber post-warm is adopted into the existing
    bucket (B_pad rung has headroom) with zero backend compiles; a
    structural bump (brand-new series) evicts to the loud cold-rebuild
    path and the fleet re-adopts with parity intact."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    mesh = _mesh8()
    rrc.GLOBAL.reset()
    s = Storage(str(tmp_path / "s"))
    try:
        t0 = _grid_t0()
        last, rng = _seed(s, t0)
        end = _end0(t0)
        engine = TPUEngine(min_series=4, mesh=mesh)
        api = PrometheusAPI(s, engine)
        panels = PANELS[:3]
        subs = [api.matstreams.subscribe(q, STEP, DUR) for q in panels]
        clis = [StreamClient() for _ in panels]
        for sub, cli in zip(subs, clis):
            cli.apply(sub.next_frame(timeout_s=10.0, now_ms=end))
        plane = engine.fleet()
        for _ in range(2):  # warm the buckets
            end += STEP
            _ingest(s, rng, last, end)
            _pump(subs, clis, end)
        warm = plane.stats()
        assert warm["members"] == 3, warm

        # (a) a new same-shaped subscriber: adopted, ZERO new compiles
        q_new = "avg by (g)(rate(fl_m[5m]))"
        sub_new = api.matstreams.subscribe(q_new, STEP, DUR)
        cli_new = StreamClient()
        cli_new.apply(sub_new.next_frame(timeout_s=10.0, now_ms=end))
        panels = panels + [q_new]
        subs.append(sub_new)
        clis.append(cli_new)
        for _ in range(2):
            end += STEP
            _ingest(s, rng, last, end)
            _pump(subs, clis, end)
        st = plane.stats()
        assert st["members"] == 4, st
        assert st["buckets"] == warm["buckets"], st
        assert st["compiles"] - warm["compiles"] == 0, (
            "adopting a same-shaped subscriber recompiled the bucket")

        # (b) structural churn: a NEW series bumps the structural
        # version, evicting every member to the loud cold-rebuild path
        # (S 16 -> 17 also crosses the S rung, so the re-adopted members
        # land in fresh buckets); the fleet re-adopts within the
        # post-eviction retry budget and parity holds again
        s.add_rows([({"__name__": "fl_m", "i": str(NS), "g": "g0"},
                     end + (j + 1) * SCRAPE, float(j)) for j in range(4)])
        last = np.append(last, 3.0)
        for _ in range(3):
            end += STEP
            _ingest(s, rng, last, end, ns=NS + 1)
            _pump(subs, clis, end)
        st2 = plane.stats()
        assert st2["members"] == 4, (
            f"fleet did not re-adopt after structural churn: {st2}")
        for q, cli in zip(panels, clis):
            _assert_close(_np_rows(cli.result()),
                          polled(s, q, end - DUR, end, STEP),
                          ctx=f"post-churn {q!r}")
    finally:
        s.close()


def test_fleet_cost_split_sums_to_launch_total(tmp_path, monkeypatch):
    """Per-stream cost attribution: the rows-share split of each shared
    launch lands in the streams' usage rows (deviceExecMs) and sums to
    the measured launch wall — the last member takes the exact
    remainder, so nothing is lost or double-billed."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    from victoriametrics_tpu.utils import flightrec
    mesh = _mesh8()
    rrc.GLOBAL.reset()
    s = Storage(str(tmp_path / "s"))
    walls = []
    orig_rec = flightrec.rec

    def spy(name, t0, dur, *rest, **kw):
        if name == "device:fleet_launch":
            walls.append(dur)
        return orig_rec(name, t0, dur, *rest, **kw)

    monkeypatch.setattr(flightrec, "rec", spy)
    try:
        t0 = _grid_t0()
        last, rng = _seed(s, t0)
        end = _end0(t0)
        engine = TPUEngine(min_series=4, mesh=mesh)
        api = PrometheusAPI(s, engine)
        subs = [api.matstreams.subscribe(q, STEP, DUR) for q in PANELS]
        clis = [StreamClient() for _ in PANELS]
        for sub, cli in zip(subs, clis):
            cli.apply(sub.next_frame(timeout_s=10.0, now_ms=end))

        def exec_ms():
            return sum(ms.usage_row().get("deviceExecMs", 0.0)
                       for ms in api.matstreams.streams())

        plane = engine.fleet()
        for r in range(1, 4):
            end += STEP
            _ingest(s, rng, last, end)
            walls.clear()
            e0 = exec_ms()
            st0 = plane.stats()
            _pump(subs, clis, end)
            if r < 2 or plane.stats()["served"] - st0["served"] != \
                    len(PANELS):
                continue  # adoption interval: shares partly pre-fleet
            billed = exec_ms() - e0
            launched = sum(walls) * 1e3
            assert launched > 0, "no fleet launch recorded"
            assert abs(billed - launched) < 0.05 + 0.002 * len(PANELS), (
                f"interval {r}: usage rows billed {billed:.3f}ms for "
                f"{launched:.3f}ms of shared launches")
    finally:
        s.close()


@pytest.mark.race
class TestFleetRace:
    def test_concurrent_pumps_ingest_churn(self, tmp_path):
        """Race stress (tools/race.sh): subscriber churn + live ingest +
        concurrent cooperative pumps while the fleet plane adopts,
        launches and serves; the steady subscriber keeps advancing, no
        exception escapes, and the quiesced state matches the host
        oracle numerically."""
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        mesh = _mesh8()
        rrc.GLOBAL.reset()
        s = Storage(str(tmp_path / "s"))
        q_steady = PANELS[0]
        try:
            t0 = _grid_t0()
            _seed(s, t0)
            end0 = _end0(t0)
            engine = TPUEngine(min_series=4, mesh=mesh)
            api = PrometheusAPI(s, engine)
            steady = api.matstreams.subscribe(q_steady, STEP, DUR)
            cli = StreamClient()
            cli.apply(steady.next_frame(timeout_s=10.0, now_ms=end0))
            stop = threading.Event()
            errors: list = []
            now_box = [end0]

            def ingester():
                # idempotent values (pure function of the timestamp):
                # rewrites racing an advance stay invisible to the final
                # poll-vs-push comparison
                while not stop.is_set():
                    end = now_box[0] + STEP
                    s.add_rows([
                        ({"__name__": "fl_m", "i": str(i), "g": f"g{i % 4}"},
                         end - STEP + (k + 1) * SCRAPE,
                         float((end // SCRAPE + k) % 1000))
                        for i in range(NS) for k in range(4)])
                    time.sleep(0.002)

            def churner():
                try:
                    while not stop.is_set():
                        sub = api.matstreams.subscribe(
                            "max by (g)(rate(fl_m[5m]))", STEP, DUR)
                        sub.next_frame(timeout_s=0.05, now_ms=now_box[0])
                        sub.close()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            def pumper():
                try:
                    while not stop.is_set():
                        api.matstreams.advance_due(now_box[0])
                        time.sleep(0.001)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=f, daemon=True)
                       for f in (ingester, churner, pumper, pumper)]
            for t in threads:
                t.start()
            end = end0
            try:
                for _ in range(4):
                    end += STEP
                    now_box[0] = end
                    deadline = time.monotonic() + 30.0
                    while time.monotonic() < deadline:
                        f = steady.next_frame(timeout_s=0.2, now_ms=end)
                        if f is not None:
                            cli.apply(f)
                        if cli.window and cli.window[1] >= end:
                            break
                    assert cli.window and cli.window[1] >= end, (
                        "stream stopped advancing under concurrency")
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=10)
            assert not errors, errors
            # quiesced: one final advance sees the final data, then the
            # oracle must hold (numerically; device vs host summation
            # order differs at the last ulp)
            end += STEP
            api.matstreams.advance_due(end)
            while True:
                f = steady.next_frame(timeout_s=0.0, now_ms=end)
                if f is None:
                    break
                cli.apply(f)
            assert cli.window[1] == end
            _assert_close(_np_rows(cli.result()),
                          polled(s, q_steady, cli.window[0], cli.window[1],
                                 STEP), ctx="post-quiesce")
            steady.close()
        finally:
            s.close()


def test_bucket_up_ladder_makes_progress_from_floor_one():
    # regression: cumulative floored multiplies stalled forever at b=1
    # (1*3//2 == 1), hanging any 1-device mesh or VM_FLEET_LADDER_MIN=1
    assert [fleetmod.bucket_up(n, 1) for n in range(1, 10)] == \
        [1, 2, 3, 4, 6, 6, 8, 8, 12]
    # rungs for floors >= 2 are the documented {1, 1.5} * 2^k ladder
    assert [fleetmod.bucket_up(n, 2) for n in (2, 3, 5, 7, 13, 17)] == \
        [2, 3, 6, 8, 16, 24]
    assert [fleetmod.bucket_up(n, 8) for n in (1, 9, 17, 25)] == \
        [8, 12, 24, 32]
    for m in (1, 2, 8):
        prev = 0
        for n in range(1, 600):
            b = fleetmod.bucket_up(n, m)
            assert b >= n and b >= prev
            prev = b
