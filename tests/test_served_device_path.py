"""The HTTP result cache's suffix eval over the DEVICE engine: served
refreshes must match a cold evaluation within the f32 tile bound.

Regression: layering the device rolling tail-reuse under the result
cache's own tail merge mis-advanced reused columns when BOTH grid edges
move (~35% rate error on the reused suffix columns). The suffix eval now
sets EvalConfig.no_device_roll (fresh fused tiles, no roll/aux reuse)."""

import time

import numpy as np
import pytest

from victoriametrics_tpu import native
from victoriametrics_tpu.httpapi.prometheus_api import PrometheusAPI
from victoriametrics_tpu.query.exec import exec_query
from victoriametrics_tpu.query.tpu_engine import TPUEngine
from victoriametrics_tpu.query.types import EvalConfig
from victoriametrics_tpu.storage.storage import Storage

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs native lib")

NS, NN, STEP = 256, 360, 60_000
JITTER_MS = 2_000  # must match every rng.integers jitter below


def test_direct_advancing_refresh_matches_cold_on_device(tmp_path):
    """Direct full evals with BOTH grid edges advancing (the uncacheable-
    query dashboard pattern, which bypasses the HTTP result cache) take
    the device rolling-reuse path and must match cold evals — this is
    the constant-shape advance the rolling tile is designed for, distinct
    from the variable-length suffix grids no_device_roll guards."""
    now = int(time.time() * 1000)
    t0 = (now - (NN - 1) * 15_000) // STEP * STEP
    rng = np.random.default_rng(1)
    s = Storage(str(tmp_path / "s"))
    try:
        base = np.arange(NN, dtype=np.int64) * 15_000 + t0
        keys = [f'da{{idx="{i}",instance="h-{i % 16}"}}'.encode()
                for i in range(NS)]
        keybuf = b"".join(keys)
        klens = np.fromiter((len(k) for k in keys), np.int64, NS)
        koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
        ts2 = np.sort(base[None, :] +
                      rng.integers(-JITTER_MS, JITTER_MS + 1, (NS, NN)), axis=1)
        vals2 = np.cumsum(rng.integers(0, 50, (NS, NN)),
                          axis=1).astype(np.float64)
        s.add_rows_columnar(native.ColumnarRows(
            keybuf, np.repeat(koffs, NN), np.repeat(klens, NN),
            ts2.reshape(-1), vals2.reshape(-1)))
        s.force_flush()
        last = vals2[:, -1]
        eng = TPUEngine(value_dtype=np.float32, min_series=2)
        q = "sum by (instance)(rate(da[5m]))"
        dur = (NN - 1) * 15_000 - 300_000
        # round UP past all initial jittered samples (counter
        # monotonicity across the first refresh)
        end = t0 + -(-((NN - 1) * 15_000 + JITTER_MS) // STEP) * STEP
        kw = dict(step=STEP, storage=s, tpu=eng)
        exec_query(EvalConfig(start=end - dur, end=end, **kw), q)
        prev_warm = None
        for _ in range(3):
            end += STEP
            incr = rng.integers(0, 50, (NS, 4))
            v2 = last[:, None] + np.cumsum(incr, axis=1)
            last = v2[:, -1]
            tsf = (end - STEP +
                   (np.arange(4, dtype=np.int64) + 1)[None, :] * 15_000 +
                   rng.integers(-JITTER_MS, JITTER_MS + 1, (NS, 4)))
            tsf.sort(axis=1)
            s.add_rows_columnar(native.ColumnarRows(
                keybuf, np.repeat(koffs, 4), np.repeat(klens, 4),
                tsf.reshape(-1), v2.reshape(-1).astype(np.float64)))
            warm = exec_query(EvalConfig(start=end - dur, end=end, **kw),
                              q)
            cold = exec_query(EvalConfig(start=end - dur, end=end, **kw,
                                         disable_cache=True), q)
            dw = {ts.metric_name.marshal(): ts.values for ts in warm}
            dc = {ts.metric_name.marshal(): ts.values for ts in cold}
            assert set(dw) == set(dc)
            for k, vw in dw.items():
                vc = dc[k]
                np.testing.assert_array_equal(np.isnan(vw), np.isnan(vc))
                # The rolling path trades a bounded drift for zero
                # refetch: reused columns keep the scrape-interval
                # estimates they were computed under (the reference
                # rollupResultCache contract, rollup_result_cache.go:283)
                # and the tail kernel's estimate-dependent prev-sample
                # gating can flip vs a cold fresh-tile eval under
                # jittered scrape intervals. Bound: one gated sample's
                # worth of increase per 5m window (~scrape_interval /
                # window = 15/300), on a small fraction of columns.
                m = ~np.isnan(vw)
                rel = np.abs(vw[m] - vc[m]) / np.maximum(
                    np.abs(vc[m]), 1e-9)
                assert float(rel.max()) < 0.06, float(rel.max())
                assert (rel > 1e-4).mean() < 0.05
            if prev_warm is not None:
                # shift consistency: reused columns == previously served
                for k, vw in dw.items():
                    pv = prev_warm.get(k)
                    if pv is None:
                        continue
                    a, b = vw[:-1], pv[1:]
                    mm = ~np.isnan(a) & ~np.isnan(b)
                    np.testing.assert_array_equal(a[mm], b[mm])
            prev_warm = dw
    finally:
        s.close()


def test_served_refresh_matches_cold_on_device(tmp_path):
    now = int(time.time() * 1000)
    t0 = (now - (NN - 1) * 15_000) // STEP * STEP
    rng = np.random.default_rng(0)
    s = Storage(str(tmp_path / "s"))
    try:
        base = np.arange(NN, dtype=np.int64) * 15_000 + t0
        keys = [f'dv{{idx="{i}",instance="h-{i % 16}"}}'.encode()
                for i in range(NS)]
        keybuf = b"".join(keys)
        klens = np.fromiter((len(k) for k in keys), np.int64, NS)
        koffs = np.concatenate([[0], np.cumsum(klens)[:-1]])
        ts2 = np.sort(base[None, :] +
                      rng.integers(-JITTER_MS, JITTER_MS + 1, (NS, NN)), axis=1)
        vals2 = np.cumsum(rng.integers(0, 50, (NS, NN)),
                          axis=1).astype(np.float64)
        s.add_rows_columnar(native.ColumnarRows(
            keybuf, np.repeat(koffs, NN), np.repeat(klens, NN),
            ts2.reshape(-1), vals2.reshape(-1)))
        s.force_flush()
        last = vals2[:, -1]
        eng = TPUEngine(value_dtype=np.float32, min_series=2)
        api = PrometheusAPI(s, eng)
        q = "sum by (instance)(rate(dv[5m]))"
        dur = (NN - 1) * 15_000 - 300_000
        # round UP past all initial jittered samples (counter
        # monotonicity across the first refresh)
        end = t0 + -(-((NN - 1) * 15_000 + JITTER_MS) // STEP) * STEP
        kw = dict(step=STEP, storage=s, tpu=eng)
        api._exec_range_cached(EvalConfig(start=end - dur, end=end, **kw),
                               q, end)
        for _ in range(3):
            end += STEP
            incr = rng.integers(0, 50, (NS, 4))
            v2 = last[:, None] + np.cumsum(incr, axis=1)
            last = v2[:, -1]
            tsf = (end - STEP +
                   (np.arange(4, dtype=np.int64) + 1)[None, :] * 15_000 +
                   rng.integers(-JITTER_MS, JITTER_MS + 1, (NS, 4)))
            tsf.sort(axis=1)
            s.add_rows_columnar(native.ColumnarRows(
                keybuf, np.repeat(koffs, 4), np.repeat(klens, 4),
                tsf.reshape(-1), v2.reshape(-1).astype(np.float64)))
            rows = api._exec_range_cached(
                EvalConfig(start=end - dur, end=end, **kw), q, end)
        cold = exec_query(EvalConfig(start=end - dur, end=end, **kw,
                                     disable_cache=True), q)
        da = {ts.metric_name.marshal(): ts.values for ts in rows}
        db = {ts.metric_name.marshal(): ts.values for ts in cold}
        assert set(da) == set(db)
        for k, va in da.items():
            vb = db[k]
            fa, fb = np.isnan(va), np.isnan(vb)
            np.testing.assert_array_equal(fa, fb)
            m = ~fa
            np.testing.assert_allclose(va[m], vb[m], rtol=1e-4)
    finally:
        s.close()
