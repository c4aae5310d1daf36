"""Device rollup kernels vs the NumPy oracle, including the sharded mesh
paths on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from victoriametrics_tpu.ops import rollup_np
from victoriametrics_tpu.ops.device_rollup import (
    aggregate_groups, pack_series, rollup_aggregate_tile, rollup_tile)
from victoriametrics_tpu.ops.rollup_np import RollupConfig
from victoriametrics_tpu.parallel import mesh as meshlib

START = 1_753_700_000_000  # unix ms


def make_series(rng, n, kind="gauge", interval=15_000, jitter=True):
    ts = np.arange(n, dtype=np.int64) * interval + START
    if jitter:
        ts = ts + rng.integers(-2000, 2000, n)
        ts.sort()
    if kind == "gauge":
        v = np.round(rng.uniform(0, 100, n), 3)
    elif kind == "counter":
        v = np.cumsum(rng.integers(0, 50, n)).astype(np.float64)
    elif kind == "counter_resets":
        v = np.cumsum(rng.integers(0, 50, n)).astype(np.float64)
        for p in rng.integers(1, n, 3):
            v[p:] -= v[p]  # hard reset to 0 at p
        v = np.abs(v)
    return ts, v


CFG = RollupConfig(start=START + 600_000, end=START + 1_800_000,
                   step=60_000, window=300_000)

FUNCS = list(rollup_np.CORE_SUPPORTED)


@pytest.fixture(scope="module")
def ragged_data():
    rng = np.random.default_rng(11)
    series = []
    for i in range(17):
        kind = ("gauge", "counter", "counter_resets")[i % 3]
        n = int(rng.integers(3, 200))
        series.append(make_series(rng, n, kind))
    # edge cases: single sample, two samples, empty-window series (all before
    # query range), sparse series with big gaps
    series.append((np.array([START + 700_000]), np.array([42.0])))
    series.append((np.array([START + 700_000, START + 710_000]),
                   np.array([1.0, 5.0])))
    series.append((np.array([START - 50_000]), np.array([7.0])))
    sp_ts = np.array([START, START + 900_000, START + 1_700_000])
    series.append((sp_ts, np.array([1.0, 100.0, 3.0])))
    return series


@pytest.mark.parametrize("func", FUNCS)
def test_rollup_matches_oracle(ragged_data, func):
    series = ragged_data
    ts, vals, counts = pack_series(series, CFG.start)
    got = np.asarray(rollup_tile(func, jnp.asarray(ts), jnp.asarray(vals),
                                 jnp.asarray(counts), CFG))
    # stddev/stdvar use prefix-sum moments: ~1e-8 absolute noise relative to
    # the data scale (exactly-zero variances come back ~1e-7); all other
    # funcs must match the oracle to fp association order.
    atol = 1e-4 if func.startswith("std") else 1e-9
    for i, (s_ts, s_v) in enumerate(series):
        want = rollup_np.rollup(func, s_ts, s_v, CFG)
        np.testing.assert_allclose(
            got[i], want, rtol=1e-6 if func.startswith("std") else 1e-9,
            atol=atol, equal_nan=True, err_msg=f"series {i} func {func}")


@pytest.mark.parametrize("aggr", ["sum", "count", "avg", "min", "max", "stddev"])
def test_aggregate_groups_matches_numpy(ragged_data, aggr):
    series = ragged_data
    ts, vals, counts = pack_series(series, CFG.start)
    S = len(series)
    rng = np.random.default_rng(5)
    gids = rng.integers(0, 4, S).astype(np.int32)
    rolled = np.asarray(rollup_tile("rate", jnp.asarray(ts), jnp.asarray(vals),
                                    jnp.asarray(counts), CFG))
    got = np.asarray(aggregate_groups(aggr, jnp.asarray(rolled),
                                      jnp.asarray(gids), 4))
    T = rolled.shape[1]
    want = np.full((4, T), np.nan)
    for g in range(4):
        rows = rolled[gids == g]
        for t in range(T):
            col = rows[:, t]
            col = col[~np.isnan(col)]
            if col.size == 0:
                continue
            want[g, t] = dict(
                sum=col.sum(), count=float(col.size), avg=col.mean(),
                min=col.min(), max=col.max(), stddev=col.std())[aggr]
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9, equal_nan=True)


def test_fused_tile_equals_two_stage(ragged_data):
    series = ragged_data
    ts, vals, counts = pack_series(series, CFG.start)
    gids = np.arange(len(series), dtype=np.int32) % 3
    fused = np.asarray(rollup_aggregate_tile(
        "rate", "sum", jnp.asarray(ts), jnp.asarray(vals),
        jnp.asarray(counts), jnp.asarray(gids), CFG, 3))
    rolled = rollup_tile("rate", jnp.asarray(ts), jnp.asarray(vals),
                         jnp.asarray(counts), CFG)
    two = np.asarray(aggregate_groups("sum", rolled, jnp.asarray(gids), 3))
    np.testing.assert_allclose(fused, two, equal_nan=True)


class TestMesh:
    def _data(self, S=32, n=120):
        rng = np.random.default_rng(23)
        series = [make_series(rng, int(rng.integers(5, n)),
                              ("gauge", "counter")[i % 2]) for i in range(S)]
        ts, vals, counts = pack_series(series, CFG.start)
        gids = (np.arange(S) % 5).astype(np.int32)
        return series, ts, vals, counts, gids

    @pytest.mark.parametrize("aggr", ["sum", "avg", "max", "count"])
    def test_series_sharded_matches_single_device(self, aggr):
        series, ts, vals, counts, gids = self._data()
        mesh = meshlib.make_mesh(jax.devices()[:8])
        fn = meshlib.sharded_rollup_aggregate(mesh, "rate", aggr, CFG, 5)
        from victoriametrics_tpu.ops.device_rollup import MIN_TS_NONE
        got = np.asarray(fn(jnp.asarray(ts), jnp.asarray(vals),
                            jnp.asarray(counts), jnp.asarray(gids),
                            np.int32(0), MIN_TS_NONE, None))
        rolled = rollup_tile("rate", jnp.asarray(ts), jnp.asarray(vals),
                             jnp.asarray(counts), CFG)
        want = np.asarray(aggregate_groups(aggr, rolled, jnp.asarray(gids), 5))
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9,
                                   equal_nan=True)


class TestDeviceDecode:
    def _series(self, S=24, N=200):
        rng = np.random.default_rng(41)
        out = []
        for i in range(S):
            n = int(rng.integers(3, N))
            ts = np.arange(n, dtype=np.int64) * 15_000 + START + \
                rng.integers(-500, 500, n)
            ts.sort()
            mant = np.cumsum(rng.integers(0, 50, n)).astype(np.int64)
            out.append((ts, mant, -2))
        return out

    @pytest.mark.parametrize("func", ["rate", "sum_over_time",
                                      "max_over_time", "last_over_time"])
    def test_fused_decode_rollup_matches_dense(self, func):
        from victoriametrics_tpu.ops import device_decode as dd
        from victoriametrics_tpu.ops import decimal as dec
        series = self._series()
        planes = dd.pack_delta_planes(series, CFG.start, np.float64)
        assert planes is not None
        # plane compression actually narrows the payload
        dense_bytes = sum(t.size * 12 for t, _, _ in series)
        assert planes.nbytes < dense_bytes / 2
        n = int(planes.counts.max())
        got = np.asarray(dd.decode_and_rollup(
            func, jnp.asarray(planes.ts_first), jnp.asarray(planes.ts_fdelta),
            jnp.asarray(planes.ts_d2), jnp.asarray(planes.val_first),
            jnp.asarray(planes.val_fdelta), jnp.asarray(planes.val_d2),
            jnp.asarray(planes.scale), jnp.asarray(planes.counts),
            CFG, n, np.float64))
        for i, (ts, mant, exp) in enumerate(series):
            vals = dec.decimal_to_float(
                np.pad(mant, (0, 0)), exp) if False else mant * (10.0 ** exp)
            want = rollup_np.rollup(func, ts, vals, CFG)
            np.testing.assert_allclose(got[i], want, rtol=1e-9, atol=1e-9,
                                       equal_nan=True,
                                       err_msg=f"series {i} {func}")

    def test_overflow_falls_back(self):
        from victoriametrics_tpu.ops import device_decode as dd
        series = [(np.array([START, START + 1000], dtype=np.int64),
                   np.array([0, 1 << 40], dtype=np.int64), 0)]
        assert dd.pack_delta_planes(series, CFG.start) is None


class TestRollupBatchVsLoop:
    """rollup_batch must match the per-series rollup() loop exactly for
    every SUPPORTED func on ragged, reset-y, gap-y data."""

    def _mk_series(self, seed):
        import numpy as np
        rng = np.random.default_rng(seed)
        series = []
        T0 = 1_753_700_000_000
        for s in range(37):
            n = int(rng.integers(1, 60))
            # jittered 15s cadence with occasional gaps
            gaps = rng.integers(1, 5, n).cumsum()
            ts = T0 - 900_000 + gaps * 15_000 + rng.integers(-500, 500, n)
            ts.sort()
            if rng.random() < 0.5:
                vals = rng.integers(0, 50, n).cumsum().astype(float)
                if n > 5 and rng.random() < 0.5:
                    vals[n // 2:] -= vals[n // 2]  # counter reset
            else:
                vals = rng.normal(100, 10, n)
            series.append((ts.astype(np.int64), vals.astype(np.float64)))
        return series

    def test_all_supported_funcs_match(self):
        import numpy as np
        from victoriametrics_tpu.ops import rollup_np
        from victoriametrics_tpu.ops.rollup_np import RollupConfig, rollup
        T0 = 1_753_700_000_000
        cfg = RollupConfig(start=T0 - 600_000, end=T0, step=60_000,
                           window=120_000)
        cfg2 = RollupConfig(start=T0 - 600_000, end=T0, step=60_000,
                            window=0)  # lookback = step
        for seed in (0, 1):
            series = self._mk_series(seed)
            for c in (cfg, cfg2):
                for func in rollup_np.CORE_SUPPORTED:
                    batch = rollup_np.rollup_batch(func, series, c)
                    assert batch is not None, func
                    # stddev/stdvar go through prefix sums: zero-variance
                    # windows see ~1e-7 absolute noise (documented; far
                    # below metric precision)
                    atol = (1e-5 if func in ("stddev_over_time",
                                             "stdvar_over_time") else 1e-9)
                    for s, (ts, vals) in enumerate(series):
                        want = rollup(func, ts, vals, c)
                        got = batch[s]
                        np.testing.assert_allclose(
                            got, want, rtol=1e-6, atol=atol, equal_nan=True,
                            err_msg=f"{func} seed={seed} series={s}")

    def test_nan_values_fall_back(self):
        import numpy as np
        from victoriametrics_tpu.ops import rollup_np
        from victoriametrics_tpu.ops.rollup_np import RollupConfig
        T0 = 1_753_700_000_000
        cfg = RollupConfig(start=T0, end=T0 + 60_000, step=60_000,
                           window=120_000)
        series = [(np.array([T0 - 10_000, T0 - 5_000], dtype=np.int64),
                   np.array([1.0, np.nan]))]
        assert rollup_np.rollup_batch("sum_over_time", series, cfg) is None


class TestFusedDeviceAggr:
    """_try_device_fused_aggr must match the host aggregation exactly."""

    @pytest.fixture(scope="class")
    def store(self, tmp_path_factory):
        import numpy as np
        from victoriametrics_tpu.storage.storage import Storage
        s = Storage(str(tmp_path_factory.mktemp("fused") / "s"))
        rng = np.random.default_rng(7)
        T0 = 1_753_700_000_000
        rows = []
        for i in range(96):
            base = np.arange(60, dtype=np.int64) * 15_000 + T0 - 600_000
            ts = np.sort(base + rng.integers(-2000, 2001, 60))
            vals = np.cumsum(rng.integers(0, 30, 60)).astype(float)
            lab = {"__name__": "fm", "instance": f"h{i % 8}",
                   "job": f"j{i % 3}"}
            rows.extend(zip([lab] * 60, ts.tolist(), vals.tolist()))
        s.add_rows(rows)
        s.force_flush()
        yield s
        s.close()

    @pytest.mark.parametrize("q", [
        "sum by (instance)(rate(fm[5m]))",
        "avg by (job)(increase(fm[3m]))",
        "count(last_over_time(fm[2m]))",
        "max by (instance,job)(delta(fm[4m]))",
        "min without (job,instance)(rate(fm[5m]))",
        "stddev by (job)(avg_over_time(fm[5m]))",
        "quantile(0.9, rate(fm[5m])) by (instance)",
        "quantile(0.25, last_over_time(fm[2m])) by (job)",
        "quantile(1.5, rate(fm[5m])) by (job)",
        "median(increase(fm[3m])) by (instance)",
        "quantile(0.5, rate(fm[5m]))",
    ])
    def test_fused_matches_host(self, store, q):
        import numpy as np
        from victoriametrics_tpu.query.exec import exec_query
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        from victoriametrics_tpu.query.types import EvalConfig
        T0 = 1_753_700_000_000
        kw = dict(start=T0 - 300_000, end=T0, step=60_000, storage=store)
        host = exec_query(EvalConfig(**kw), q)
        dev = exec_query(EvalConfig(**kw, tpu=TPUEngine(min_series=4)), q)
        assert len(dev) == len(host) and len(host) > 0
        hm = {r.metric_name.marshal(): r.values for r in host}
        dm = {r.metric_name.marshal(): r.values for r in dev}
        assert set(hm) == set(dm)
        for k in hm:
            np.testing.assert_allclose(dm[k], hm[k], rtol=1e-6, atol=1e-6,
                                       equal_nan=True, err_msg=q)


    @pytest.mark.parametrize("q", [
        "topk(3, rate(fm[5m]))",
        "bottomk(3, rate(fm[5m]))",
        "topk(5, fm)",
        "bottomk(120, rate(fm[5m]))",        # k > S: keep everything
        "topk_max(4, rate(fm[5m]))",
        "topk_min(4, increase(fm[3m]))",
        "topk_avg(6, rate(fm[5m]))",
        "topk_median(4, rate(fm[5m]))",
        "topk_last(4, last_over_time(fm[2m]))",
        "bottomk_max(4, rate(fm[5m]))",
        "bottomk_avg(3, rate(fm[5m]))",
        "topk(0, rate(fm[5m]))",
    ])
    def test_topk_matches_host(self, store, q):
        """Device topk selection (topk_select_tile/rank_tile) must pick the
        same series with the same masked values as _eval_topk_family."""
        import numpy as np
        from victoriametrics_tpu.query.exec import exec_query
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        from victoriametrics_tpu.query.types import EvalConfig
        T0 = 1_753_700_000_000
        kw = dict(start=T0 - 300_000, end=T0, step=60_000, storage=store)
        host = exec_query(EvalConfig(**kw), q)
        dev = exec_query(EvalConfig(**kw, tpu=TPUEngine(min_series=4)), q)
        assert len(dev) == len(host)
        hm = {r.metric_name.marshal(): r.values for r in host}
        dm = {r.metric_name.marshal(): r.values for r in dev}
        assert set(hm) == set(dm)
        for k in hm:
            np.testing.assert_allclose(dm[k], hm[k], rtol=1e-6, atol=1e-6,
                                       equal_nan=True, err_msg=q)

    def test_topk_decline_rolls_back_sample_count(self, store):
        """A device decline (min_series too high) must not double-count
        samples against maxSamplesPerQuery when the host path re-fetches."""
        from victoriametrics_tpu.query.exec import exec_query
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        from victoriametrics_tpu.query.types import EvalConfig
        T0 = 1_753_700_000_000
        # 96 series x <=60 samples: cap at ~1.5x one fetch — double
        # counting would blow it
        kw = dict(start=T0 - 300_000, end=T0, step=60_000, storage=store,
                  max_samples_per_query=9_000)
        out = exec_query(EvalConfig(**kw, tpu=TPUEngine(min_series=10_000)),
                         "topk(3, rate(fm[5m]))")
        host = exec_query(EvalConfig(**kw), "topk(3, rate(fm[5m]))")
        assert len(out) == len(host) > 0

    def test_fused_warm_path_matches(self, store):
        """Second run hits the aux/resident-tile shortcut and must agree."""
        import numpy as np
        from victoriametrics_tpu.query.exec import exec_query
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        from victoriametrics_tpu.query.types import EvalConfig
        T0 = 1_753_700_000_000
        engine = TPUEngine(min_series=4)
        for q in ("sum by (instance)(rate(fm[5m]))",
                  "quantile(0.9, rate(fm[5m])) by (instance)"):
            kw = dict(start=T0 - 300_000, end=T0, step=60_000, storage=store)
            host = exec_query(EvalConfig(**kw), q)
            cold = exec_query(EvalConfig(**kw, tpu=engine), q)
            warm = exec_query(EvalConfig(**kw, tpu=engine), q)
            hm = {r.metric_name.marshal(): r.values for r in host}
            for res in (cold, warm):
                rm = {r.metric_name.marshal(): r.values for r in res}
                assert set(rm) == set(hm), q
                for k in hm:
                    np.testing.assert_allclose(rm[k], hm[k], rtol=1e-6,
                                               atol=1e-6, equal_nan=True,
                                               err_msg=q)
