"""Tier-1 regression guards for device-resident mesh-sharded rollup
serving (ISSUE 12): over a rolling dashboard loop on the virtual 8-device
CPU mesh, a steady-state refresh must UPLOAD only the suffix tail columns
(< 5% of the cold-window upload, by vm_device_bytes_uploaded_total) and be
served from the resident window (vm_device_window_cache_hits_total ticks).
Churn (a new series appearing) must fall back LOUDLY to the full-upload
rebuild and still agree with the VM_DEVICE_RESIDENT=0 oracle; window-slide
compaction (ops.device_rollup.compact_tile) must keep the window rolling
once column headroom runs out, without touching results.

Mirrors tests/test_refresh_suffix_guard.py on the device plane."""

import time

import numpy as np
import pytest

from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
from victoriametrics_tpu.models import tile_cache as tclib
from victoriametrics_tpu.query import rollup_result_cache as rrc
from victoriametrics_tpu.query.exec import exec_query
from victoriametrics_tpu.query.types import EvalConfig
from victoriametrics_tpu.storage.storage import Storage
from victoriametrics_tpu.utils import metrics as metricslib

STEP = 60_000
SCRAPE = 15_000
NS = 64
NN = 1440
Q = "sum by (g)(rate(resg[5m]))"


def _mesh8():
    import jax

    from victoriametrics_tpu.parallel.mesh import make_mesh
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(devs[:8])


def _mk_store(path, n_samples=NN, name="resg"):
    s = Storage(str(path))
    now = int(time.time() * 1000)
    t0 = (now - (n_samples - 1) * SCRAPE) // STEP * STEP
    rng = np.random.default_rng(5)
    rows = []
    vals0 = np.empty(NS)
    for i in range(NS):
        ts = np.sort(np.arange(n_samples, dtype=np.int64) * SCRAPE + t0 +
                     rng.integers(-2000, 2001, n_samples))
        vals = np.cumsum(rng.integers(0, 30, n_samples)).astype(np.float64)
        vals0[i] = vals[-1]
        rows.extend(zip([{"__name__": name, "i": str(i),
                          "g": f"g{i % 4}"}] * n_samples,
                        ts.tolist(), vals.tolist()))
    s.add_rows(rows)
    s.force_flush()
    # first window end: past every jittered initial sample
    end0 = t0 + -(-((n_samples - 1) * SCRAPE + 2000) // STEP) * STEP
    return s, end0, vals0, rng


def _ingest(s, rng, vals0, end, name="resg", k=4, scrape=SCRAPE,
            n_series=NS):
    """k fresh scrapes per series in (end - k*scrape, end]."""
    rows = []
    for i in range(n_series):
        incr = np.cumsum(rng.integers(0, 30, k))
        ts = end - (np.arange(k, dtype=np.int64)[::-1]) * scrape - \
            rng.integers(0, 2000)
        rows.extend(zip([{"__name__": name, "i": str(i),
                          "g": f"g{i % 4}"}] * k,
                        ts.tolist(), (vals0[i] + incr).tolist()))
        vals0[i] += incr[-1]
    s.add_rows(rows)


def _as_map(rows):
    return {r.metric_name.marshal(): np.asarray(r.values) for r in rows}


def test_refresh_uploads_only_tail_on_mesh(tmp_path):
    """THE residency guard: rolling refreshes on the virtual 8-device mesh
    upload < 5% of the cold-window upload each, and the resident-window
    hit counter ticks every refresh."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    mesh = _mesh8()
    s, end, vals0, rng = _mk_store(tmp_path / "s")
    try:
        rrc.GLOBAL.reset()
        engine = TPUEngine(min_series=4, mesh=mesh)
        api = PrometheusAPI(s, engine)
        dur = (NN - 1) * SCRAPE // STEP * STEP - 10 * STEP
        kw = dict(step=STEP, storage=s, tpu=engine)
        up0 = tclib.bytes_uploaded()
        # warm-up: cold full-window eval builds the resident sharded
        # window (and pays the full upload ONCE)
        api._exec_range_cached(EvalConfig(start=end - dur, end=end, **kw),
                               Q, end)
        cold_upload = tclib.bytes_uploaded() - up0
        assert cold_upload > 0
        hits0 = metricslib.REGISTRY.counter(
            "vm_device_window_cache_hits_total").get()
        for r in range(3):
            end += STEP
            _ingest(s, rng, vals0, end)
            up_r = tclib.bytes_uploaded()
            served = api._exec_range_cached(
                EvalConfig(start=end - dur, end=end, **kw), Q, end)
            refresh_upload = tclib.bytes_uploaded() - up_r
            assert len(served) == 4
            # THE guard: a refresh must ship only tail columns
            assert refresh_upload < 0.05 * cold_upload, (
                f"refresh {r} uploaded {refresh_upload} bytes "
                f"(cold window = {cold_upload}): device serving has "
                "regressed to full re-upload")
        hits = metricslib.REGISTRY.counter(
            "vm_device_window_cache_hits_total").get()
        assert hits >= hits0 + 3, "resident-window hits did not tick"
        # the resident window really is mesh-sharded
        from victoriametrics_tpu.query.tpu_engine import RollingTile
        rts = [v for v in engine.window_cache()._entries.values()
               if isinstance(v, RollingTile)]
        assert rts and len(rts[0].tiles[0].sharding.device_set) == 8
    finally:
        s.close()


def _run_sequence(tmp_path, sub, mesh, churn=False):
    """One deterministic rolling sequence; returns the per-refresh row
    maps.  churn=True ingests a NEW series before the last refresh (the
    loud-fallback case)."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    s, end, vals0, rng = _mk_store(tmp_path / sub, n_samples=240)
    try:
        rrc.GLOBAL.reset()
        engine = TPUEngine(min_series=4, mesh=mesh)
        api = PrometheusAPI(s, engine)
        dur = 239 * SCRAPE // STEP * STEP - 10 * STEP
        kw = dict(step=STEP, storage=s, tpu=engine)
        api._exec_range_cached(EvalConfig(start=end - dur, end=end, **kw),
                               Q, end)
        out = []
        churn_pair = None
        for r in range(3):
            end += STEP
            _ingest(s, rng, vals0, end)
            if churn and r == 2:
                # a brand-new series appears: advance must decline loudly
                # and rebuild via the full-upload path
                s.add_rows([({"__name__": "resg", "i": "new", "g": "g0"},
                             end - 7_000, 1.0)])
            rows = api._exec_range_cached(
                EvalConfig(start=end - dur, end=end, **kw), Q, end)
            out.append(_as_map(rows))
            if churn and r == 2:
                # the fallback rebuild must BE the cold full-upload eval:
                # a fresh nocache eval of the same window is bit-identical
                cold = exec_query(EvalConfig(start=end - dur, end=end,
                                             **kw, disable_cache=True), Q)
                churn_pair = (out[-1], _as_map(cold))
        return out, churn_pair
    finally:
        s.close()


def test_churn_falls_back_and_matches_oracle(tmp_path, monkeypatch):
    """New-series churn: the resident window declines, rebuilds full, and
    every refresh agrees with the VM_DEVICE_RESIDENT=0 full-upload oracle
    (bit-exact on the rebuild refresh; rtol=1e-12 on resident refreshes —
    XLA orders group sums differently across suffix/full grids)."""
    mesh = _mesh8()
    got, churn_pair = _run_sequence(tmp_path, "a", mesh, churn=True)
    # loud fallback really is the full-upload path: the churn refresh is
    # bit-identical to a fresh nocache eval of the same window
    served_map, cold_map = churn_pair
    assert set(served_map) == set(cold_map)
    for k in served_map:
        np.testing.assert_array_equal(served_map[k], cold_map[k])
    monkeypatch.setenv("VM_DEVICE_RESIDENT", "0")
    want, _ = _run_sequence(tmp_path, "b", mesh, churn=True)
    assert len(got) == len(want)
    for r, (gm, wm) in enumerate(zip(got, want)):
        assert set(gm) == set(wm), r
        for k in gm:
            # rtol=1e-12: the oracle serves through the host ring cache
            # (suffix grids), the resident path through the rolling
            # window — XLA orders group sums differently per grid shape
            fa, fb = np.isnan(gm[k]), np.isnan(wm[k])
            np.testing.assert_array_equal(fa, fb)
            np.testing.assert_allclose(gm[k][~fa], wm[k][~fb],
                                       rtol=1e-12, err_msg=str(r))


def test_oracle_disables_resident_reuse(tmp_path, monkeypatch):
    """VM_DEVICE_RESIDENT=0: no resident-window hits, every refresh
    re-uploads (the loud escape hatch really is a full-upload path)."""
    mesh = _mesh8()
    monkeypatch.setenv("VM_DEVICE_RESIDENT", "0")
    hits0 = metricslib.REGISTRY.counter(
        "vm_device_window_cache_hits_total").get()
    _run_sequence(tmp_path, "c", mesh)
    assert metricslib.REGISTRY.counter(
        "vm_device_window_cache_hits_total").get() == hits0


def test_refresh_finds_its_series_plan(tmp_path):
    """The host side of the same loop: a refresh's tail fetch asks for
    the series set the refresh before it asked for, so from the second
    refresh on it finds its series plan
    (vm_fetch_plan_total{result="hit"} ticks) and does not derive the
    panel's names again; the served answer still equals a cold eval."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    s, end, vals0, rng = _mk_store(tmp_path / "s", n_samples=240)
    if (end - 20 * STEP) // 86_400_000 != (end + 3 * STEP) // 86_400_000:
        s.close()
        pytest.skip("the loop would cross midnight UTC: a new day's "
                    "index is a new series list, rightly a miss")
    try:
        rrc.GLOBAL.reset()
        engine = TPUEngine(min_series=4)
        api = PrometheusAPI(s, engine)
        dur = 239 * SCRAPE // STEP * STEP - 10 * STEP
        kw = dict(step=STEP, storage=s, tpu=engine)
        api._exec_range_cached(EvalConfig(start=end - dur, end=end, **kw),
                               Q, end)
        plan = {r: metricslib.REGISTRY.counter(
            f'vm_fetch_plan_total{{result="{r}"}}') for r in ("hit", "miss")}
        win0 = metricslib.REGISTRY.counter(
            "vm_device_window_cache_hits_total").get()
        for r in range(3):
            end += STEP
            _ingest(s, rng, vals0, end)
            hit0, miss0 = plan["hit"].get(), plan["miss"].get()
            served = api._exec_range_cached(
                EvalConfig(start=end - dur, end=end, **kw), Q, end)
            if r:
                assert plan["hit"].get() > hit0, f"refresh {r + 1}: no hit"
                assert plan["miss"].get() == miss0, f"refresh {r + 1}"
            cold = exec_query(EvalConfig(start=end - dur, end=end, **kw,
                                         disable_cache=True), Q)
            gm, cm = _as_map(served), _as_map(cold)
            assert set(gm) == set(cm) and len(gm) == 4
            for k in gm:
                fa = np.isnan(gm[k])
                np.testing.assert_array_equal(fa, np.isnan(cm[k]))
                np.testing.assert_allclose(gm[k][~fa], cm[k][~fa],
                                           rtol=1e-12, err_msg=str(r))
        assert metricslib.REGISTRY.counter(
            "vm_device_window_cache_hits_total").get() >= win0 + 3, \
            "the loop left the device path"
    finally:
        s.close()


def test_window_slide_compaction_keeps_rolling(tmp_path):
    """Column-headroom exhaustion triggers on-device compaction (samples
    older than the fetch bound dropped, origin rebased) instead of a
    rebuild: the compaction counter ticks, the window keeps advancing
    in place, and results still match a cold eval at rtol=1e-12."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    s, end, vals0, rng = _mk_store(tmp_path / "s", n_samples=80)
    try:
        rrc.GLOBAL.reset()
        engine = TPUEngine(min_series=4)
        api = PrometheusAPI(s, engine)
        q = "sum by (g)(rate(resg[2m]))"
        dur = 10 * STEP
        kw = dict(step=STEP, storage=s, tpu=engine)
        api._exec_range_cached(EvalConfig(start=end - dur, end=end, **kw),
                               q, end)
        comp0 = metricslib.REGISTRY.counter(
            "vm_device_window_compactions_total").get()
        hits0 = metricslib.REGISTRY.counter(
            "vm_device_window_cache_hits_total").get()
        # each refresh jumps 5 minutes (constant-shape advance, scrape
        # cadence unchanged): 20 new columns per refresh exhaust the
        # ~48-column headroom of an 80-sample tile within a few refreshes
        for r in range(6):
            end += 5 * STEP
            _ingest(s, rng, vals0, end, k=20, scrape=SCRAPE)
            served = api._exec_range_cached(
                EvalConfig(start=end - dur, end=end, **kw), q, end)
            cold = exec_query(EvalConfig(start=end - dur, end=end, **kw,
                                         disable_cache=True), q)
            gm, cm = _as_map(served), _as_map(cold)
            assert set(gm) == set(cm)
            for k in gm:
                fa = np.isnan(gm[k])
                np.testing.assert_array_equal(fa, np.isnan(cm[k]))
                np.testing.assert_allclose(gm[k][~fa], cm[k][~fa],
                                           rtol=1e-12, err_msg=str(r))
        assert metricslib.REGISTRY.counter(
            "vm_device_window_compactions_total").get() > comp0, \
            "headroom exhaustion never compacted"
        assert metricslib.REGISTRY.counter(
            "vm_device_window_cache_hits_total").get() >= hits0 + 6, \
            "compaction fell back to rebuild instead of keeping residency"
    finally:
        s.close()


def test_compact_tile_kernel_bitexact():
    """compact_tile == numpy reference: prefix drop + left shift + rebase,
    TS_PAD restored in freed tails."""
    import jax.numpy as jnp

    from victoriametrics_tpu.ops.device_rollup import TS_PAD, compact_tile
    rng = np.random.default_rng(9)
    S, N = 5, 32
    counts = rng.integers(0, N + 1, S).astype(np.int32)
    ts = np.full((S, N), TS_PAD, np.int32)
    vals = np.zeros((S, N))
    for i in range(S):
        ts[i, :counts[i]] = np.sort(rng.integers(0, 10_000, counts[i]))
        vals[i, :counts[i]] = rng.normal(size=counts[i])
    cutoff, delta = np.int32(4_000), np.int32(4_000)
    ts2, v2, c2 = compact_tile(jnp.asarray(ts), jnp.asarray(vals),
                               jnp.asarray(counts), cutoff, delta)
    ts2, v2, c2 = np.asarray(ts2), np.asarray(v2), np.asarray(c2)
    for i in range(S):
        keep = ts[i, :counts[i]] >= cutoff
        want_ts = ts[i, :counts[i]][keep] - delta
        want_v = vals[i, :counts[i]][keep]
        assert c2[i] == keep.sum()
        np.testing.assert_array_equal(ts2[i, :c2[i]], want_ts)
        np.testing.assert_array_equal(v2[i, :c2[i]], want_v)
        assert (ts2[i, c2[i]:] == TS_PAD).all()


def test_compact_window_declines_past_int32(tmp_path):
    """A cutoff beyond the int32 frame (dashboard resumed after a very
    long pause on an old tile) must DECLINE — not raise OverflowError —
    and must not touch the tile state."""
    import jax.numpy as jnp

    from victoriametrics_tpu.ops.device_rollup import TS_PAD
    from victoriametrics_tpu.query.tpu_engine import (RollingTile,
                                                      TPUEngine,
                                                      compact_window)
    engine = TPUEngine(min_series=4)
    ts = jnp.full((2, 8), TS_PAD, jnp.int32).at[:, :3].set(
        jnp.arange(3, dtype=jnp.int32) * 1000)
    vals = jnp.zeros((2, 8))
    counts = jnp.full((2,), 3, jnp.int32)
    rt = RollingTile(tiles=(ts, vals, counts, None), base_ms=1_000_000,
                     n_cap=8, lo_ms=990_000, hi_ms=1_002_000, version=1,
                     structural=0, counts_host=np.full(2, 3, np.int64),
                     row_of_raw={}, n_samples=6, adopted_key=None)
    assert compact_window(engine, rt, 1_000_000 + 2**31 + 5) is False
    assert rt.base_ms == 1_000_000 and rt.n_samples == 6
    # and an in-range cutoff still compacts
    assert compact_window(engine, rt, 1_000_000 + 1_500) is True
    assert rt.base_ms == 1_001_500 and int(rt.counts_host.sum()) == 2


def test_persistent_churn_backs_off_to_host_suffix(tmp_path):
    """Nonstop series churn must not turn every refresh into a full-window
    device rebuild: after 2 consecutive rolling declines the serving
    layer routes the shape back to the host suffix path (O(new samples);
    small suffix-tile uploads only) until the periodic residency retry."""
    from victoriametrics_tpu.query.tpu_engine import TPUEngine
    s, end, vals0, rng = _mk_store(tmp_path / "s", n_samples=720)
    try:
        rrc.GLOBAL.reset()
        engine = TPUEngine(min_series=4)
        api = PrometheusAPI(s, engine)
        dur = 719 * SCRAPE // STEP * STEP - 10 * STEP
        kw = dict(step=STEP, storage=s, tpu=engine)
        up0 = tclib.bytes_uploaded()
        api._exec_range_cached(EvalConfig(start=end - dur, end=end, **kw),
                               Q, end)
        cold_upload = tclib.bytes_uploaded() - up0
        inpl = metricslib.REGISTRY.counter("vm_rollup_cache_inplace_total")
        inpl0 = inpl.get()
        late_uploads = []
        for r in range(5):
            end += STEP
            _ingest(s, rng, vals0, end)
            # a NEW series every refresh: the rolling advance declines
            s.add_rows([({"__name__": "resg", "i": f"new{r}", "g": "g0"},
                         end - 7_000, 1.0)])
            u0 = tclib.bytes_uploaded()
            api._exec_range_cached(
                EvalConfig(start=end - dur, end=end, **kw), Q, end)
            if r >= 2:
                late_uploads.append(tclib.bytes_uploaded() - u0)
        # after the backoff engages, refreshes must not re-upload the
        # window (suffix tiles are a fraction of the cold upload)
        for r, u in enumerate(late_uploads):
            assert u < 0.3 * cold_upload, (
                f"late refresh {r} uploaded {u}B of {cold_upload}B cold: "
                "churn backoff did not engage")
        # and they really served through the host ring cache
        assert inpl.get() > inpl0
    finally:
        s.close()
