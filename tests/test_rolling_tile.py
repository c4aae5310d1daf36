"""Rolling device tiles: a repeated fused query whose window advances while
ingest appends must be served from the HBM-resident tile via incremental
appends (device scatter + traced grid shift), not a rebuild — and must agree
with the host evaluator exactly (VERDICT r2 #1 'incremental tile
maintenance'; the reference's tail-reuse is rollup_result_cache.go:283).
"""

import numpy as np
import pytest

T0 = 1_753_700_000_000
STEP = 60_000


def _mk_store(tmp_path, n_series=80, n_samples=60):
    from victoriametrics_tpu.storage.storage import Storage
    s = Storage(str(tmp_path / "s"))
    rng = np.random.default_rng(21)
    rows = []
    for i in range(n_series):
        base = np.arange(n_samples, dtype=np.int64) * 15_000 + \
            T0 - 600_000
        ts = np.sort(base + rng.integers(-2000, 2001, n_samples))
        vals = np.cumsum(rng.integers(0, 30, n_samples)).astype(float)
        lab = {"__name__": "rt", "instance": f"h{i % 8}", "job": f"j{i % 3}"}
        rows.extend(zip([lab] * n_samples, ts.tolist(), vals.tolist()))
    s.add_rows(rows)
    s.force_flush()
    return s


def _ingest_newer(s, t_lo, n=4, n_series=80):
    rng = np.random.default_rng(int(t_lo) % 2**31)
    rows = []
    for i in range(n_series):
        ts = t_lo + np.arange(n, dtype=np.int64) * 15_000 + \
            rng.integers(0, 2000)
        vals = (1000 + np.cumsum(rng.integers(0, 30, n))).astype(float)
        lab = {"__name__": "rt", "instance": f"h{i % 8}", "job": f"j{i % 3}"}
        rows.extend(zip([lab] * n, ts.tolist(), vals.tolist()))
    s.add_rows(rows)
    s.force_flush()


def _run(store, q, engine, start, end):
    from victoriametrics_tpu.query.exec import exec_query
    from victoriametrics_tpu.query.types import EvalConfig
    kw = dict(start=start, end=end, step=STEP, storage=store)
    if engine is not None:
        kw["tpu"] = engine
    else:
        # the host oracle must be a FULL recompute: the eval rollup cache's
        # tail merge recomputes tail steps as instant sub-ranges, which
        # legitimately flips the reference's maxPrevInterval rule
        # (rollup.go:719-728) and shifts edge values
        kw["disable_cache"] = True
    return {r.metric_name.marshal(): np.asarray(r.values)
            for r in exec_query(EvalConfig(**kw), q)}


def _rolling_tiles(engine):
    # resident rolling windows live in the DeviceWindowCache now
    from victoriametrics_tpu.query.tpu_engine import RollingTile
    wc = engine._wcache
    vals = list(wc._entries.values()) if wc is not None else []
    return [v for v in vals if isinstance(v, RollingTile)]


def _check(host, dev, q=""):
    assert set(host) == set(dev) and len(host) > 0
    for k in host:
        np.testing.assert_allclose(dev[k], host[k], rtol=1e-9, atol=1e-9,
                                   equal_nan=True, err_msg=q)


QUERIES = [
    "sum by (instance)(rate(rt[5m]))",
    "avg by (job)(increase(rt[3m]))",
    "quantile(0.9, rate(rt[5m])) by (instance)",
]


class TestRollingTile:

    @pytest.mark.parametrize("q", QUERIES)
    def test_rolling_advance_matches_host(self, tmp_path, q):
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        store = _mk_store(tmp_path)
        try:
            engine = TPUEngine(min_series=4)
            # cold: builds the tile + rolling state
            _check(_run(store, q, None, T0 - 300_000, T0),
                   _run(store, q, engine, T0 - 300_000, T0), q)
            rts = _rolling_tiles(engine)
            assert len(rts) == 1
            # live ingest strictly newer than the covered range, window
            # advances one step: must append, not rebuild
            _ingest_newer(store, T0 + 10_000)
            start2, end2 = T0 - 240_000, T0 + STEP
            _check(_run(store, q, None, start2, end2),
                   _run(store, q, engine, start2, end2), q)
            assert rts[0].appends == 1, "slice was not appended on device"
            # a second advance over the same state
            _ingest_newer(store, T0 + 80_000)
            start3, end3 = T0 - 180_000, T0 + 2 * STEP
            _check(_run(store, q, None, start3, end3),
                   _run(store, q, engine, start3, end3), q)
            assert rts[0].appends == 2
        finally:
            store.close()

    def test_repeat_without_ingest_served_from_tile(self, tmp_path):
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        store = _mk_store(tmp_path)
        try:
            engine = TPUEngine(min_series=4)
            q = QUERIES[0]
            _run(store, q, engine, T0 - 300_000, T0)
            rts = _rolling_tiles(engine)
            # same end, later start: fully inside coverage, zero appends
            host = _run(store, q, None, T0 - 240_000, T0)
            dev = _run(store, q, engine, T0 - 240_000, T0)
            _check(host, dev)
            assert rts[0].appends == 0
            # end advances past the covered bound with NO new ingest: data
            # beyond the old fetch bound must still be sliced in
            host = _run(store, q, None, T0 - 240_000, T0 + STEP)
            dev = _run(store, q, engine, T0 - 240_000, T0 + STEP)
            _check(host, dev)
            assert rts[0].appends == 1
        finally:
            store.close()

    def test_late_data_forces_rebuild(self, tmp_path):
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        store = _mk_store(tmp_path)
        try:
            engine = TPUEngine(min_series=4)
            q = QUERIES[0]
            _run(store, q, engine, T0 - 300_000, T0)
            rts = _rolling_tiles(engine)
            # backfill INSIDE the covered range: the append watermark must
            # refuse the incremental path
            lab = {"__name__": "rt", "instance": "h0", "job": "j0"}
            store.add_rows([(lab, T0 - 450_000 + 7, 123.0)])
            store.force_flush()
            host = _run(store, q, None, T0 - 240_000, T0 + STEP)
            dev = _run(store, q, engine, T0 - 240_000, T0 + STEP)
            _check(host, dev)
            assert rts[0].appends == 0, "late data must not append"
        finally:
            store.close()

    def test_new_series_forces_rebuild(self, tmp_path):
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        store = _mk_store(tmp_path)
        try:
            engine = TPUEngine(min_series=4)
            q = QUERIES[0]
            _run(store, q, engine, T0 - 300_000, T0)
            rts = _rolling_tiles(engine)
            lab = {"__name__": "rt", "instance": "hNEW", "job": "jNEW"}
            ts = T0 + 10_000 + np.arange(4, dtype=np.int64) * 15_000
            store.add_rows([(lab, int(t), float(i))
                            for i, t in enumerate(ts)])
            store.force_flush()
            host = _run(store, q, None, T0 - 240_000, T0 + STEP)
            dev = _run(store, q, engine, T0 - 240_000, T0 + STEP)
            _check(host, dev)
            assert rts[0].appends == 0
        finally:
            store.close()

    def test_delete_forces_rebuild(self, tmp_path):
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        from victoriametrics_tpu.storage.tag_filters import TagFilter
        store = _mk_store(tmp_path)
        try:
            engine = TPUEngine(min_series=4)
            q = QUERIES[0]
            _run(store, q, engine, T0 - 300_000, T0)
            store.delete_series(
                [TagFilter(b"instance", b"h7", False, False)])
            host = _run(store, q, None, T0 - 240_000, T0 + STEP)
            dev = _run(store, q, engine, T0 - 240_000, T0 + STEP)
            _check(host, dev)
        finally:
            store.close()

    def test_rolling_on_mesh(self, tmp_path):
        import jax

        from victoriametrics_tpu.parallel.mesh import make_mesh
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = make_mesh(devs[:8])
        store = _mk_store(tmp_path, n_series=81)  # pad path
        try:
            engine = TPUEngine(min_series=4, mesh=mesh)
            q = QUERIES[0]
            _check(_run(store, q, None, T0 - 300_000, T0),
                   _run(store, q, engine, T0 - 300_000, T0))
            rts = _rolling_tiles(engine)
            _ingest_newer(store, T0 + 10_000, n_series=81)
            host = _run(store, q, None, T0 - 240_000, T0 + STEP)
            dev = _run(store, q, engine, T0 - 240_000, T0 + STEP)
            _check(host, dev)
            assert rts and rts[0].appends == 1
        finally:
            store.close()

    def test_old_history_prev_sample_truncation(self, tmp_path):
        """A rolling tile keeps MORE history than a later query would fetch.
        Funcs seeded by the sample before the window (delta/increase/
        changes) must behave as if that history were truncated at the
        query's fetch bound — the kernel's min_ts gate."""
        from victoriametrics_tpu.storage.storage import Storage
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        s = Storage(str(tmp_path / "s"))
        rows = []
        for i in range(70):
            lab = {"__name__": "gap", "instance": f"h{i % 7}"}
            # one OLD sample, then a long silence, then in-window samples
            rows.append((lab, T0 - 550_000 + i, 100.0 + i))
            for k in range(12):
                rows.append((lab, T0 - 180_000 + k * 15_000 + i,
                             200.0 + k + i))
        s.add_rows(rows)
        s.force_flush()
        try:
            engine = TPUEngine(min_series=4)
            for q in ("sum by (instance)(delta(gap[4m]))",
                      "sum by (instance)(increase(gap[4m]))",
                      "sum by (instance)(changes(gap[4m]))"):
                # cold query: fetch_lo reaches the old sample -> in tile
                _check(_run(s, q, None, T0 - 300_000, T0),
                       _run(s, q, engine, T0 - 300_000, T0), q)
                # advanced query: host fetch_lo = start-240k-300k excludes
                # the old sample; the tile still holds it
                start2, end2 = T0 + 60_000, T0 + 120_000
                host = _run(s, q, None, start2, end2)
                dev = _run(s, q, engine, start2, end2)
                _check(host, dev, q + " (advanced)")
            rts = _rolling_tiles(engine)
            assert rts and all(rt.appends <= 1 for rt in rts)
        finally:
            s.close()

    def test_many_advances_until_capacity(self, tmp_path):
        """Keep advancing until headroom runs out: the rebuild must be
        seamless and every step must match the host."""
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        store = _mk_store(tmp_path, n_series=70)
        try:
            engine = TPUEngine(min_series=4)
            q = QUERIES[0]
            _run(store, q, engine, T0 - 300_000, T0)
            end = T0
            # append at the data FRONTIER (store seeds through T0+285s):
            # strictly-newest regular-cadence ingest, the production rolling
            # shape. Interleaving new batches BELOW existing samples would
            # create double-density intervals whose scrape-interval
            # estimate drift flips marginal prev gates — rollup-cache
            # reused columns legitimately keep compute-time estimates
            # (rollup_result_cache.go:283 contract).
            frontier = T0 + 285_000 + 15_000
            for k in range(12):
                _ingest_newer(store, frontier, n=8, n_series=70)
                frontier += 8 * 15_000
                end += STEP * 2
                host = _run(store, q, None, end - 300_000, end)
                dev = _run(store, q, engine, end - 300_000, end)
                _check(host, dev, f"advance {k}")
        finally:
            store.close()

    def test_strides_of_one_to_three_steps_compile_nothing_new(
            self, tmp_path):
        """A panel asked at strides of one to three steps in any order
        (two panels of one dashboard asked in turn, in rounds): once a
        3-step stride has run, the window's tails and appends run the
        programs it compiled, whatever stride comes next, and every
        answer matches the host."""
        from victoriametrics_tpu.ops import device_rollup as dr
        from victoriametrics_tpu.query.tpu_engine import TPUEngine
        store = _mk_store(tmp_path, n_series=24)
        try:
            engine = TPUEngine(min_series=4)
            # increase: rate's prev-sample gate reads the scrape-interval
            # estimate, which reused columns keep from when they were
            # computed (RollingTile's accuracy contract)
            q = "sum by (instance)(increase(rt[5m]))"
            end = T0
            _run(store, q, engine, end - 300_000, end)
            frontier = T0 + 285_000 + 15_000

            def tick(k):
                nonlocal end, frontier
                _ingest_newer(store, frontier, n=4 * k, n_series=24)
                frontier += 4 * k * 15_000
                end += k * STEP
                _check(_run(store, q, None, end - 300_000, end),
                       _run(store, q, engine, end - 300_000, end),
                       f"stride {k}")

            tick(3)
            sizes = (dr.rollup_aggregate_tile._cache_size(),
                     dr.append_tile._cache_size())
            for k in (1, 2, 3, 1, 2, 1):
                tick(k)
            assert (dr.rollup_aggregate_tile._cache_size(),
                    dr.append_tile._cache_size()) == sizes
            (rt,) = _rolling_tiles(engine)
            assert rt.appends == 7
            assert (rt.tail_floor, rt.append_floor) == (4, 16)
        finally:
            store.close()


def _fresh_window():
    from victoriametrics_tpu.query.tpu_engine import RollingTile
    return RollingTile(None, T0, 0, T0, T0, 0, None, np.zeros(1, np.int64),
                       {}, 0, None)


@pytest.mark.parametrize("asks, steps", [
    ([1, 1, 1], [1, 1, 1]),             # refreshed every step: as always
    ([2, 1, 3, 1], [4, 4, 4, 4]),
    ([1, 3, 1, 2], [1, 4, 4, 4]),
    ([110, 1, 5, 1], [110, 1, 5, 1]),   # a catch-up leaves the floor
])
def test_tail_steps_stick_to_the_small_tail_the_window_ran(asks, steps):
    rt = _fresh_window()
    assert [rt.tail_steps(w) for w in asks] == steps


@pytest.mark.parametrize("asks, cols", [
    ([4, 5, 3], [8, 8, 8]),             # one step of 15 s scrapes a tick
    ([9, 5, 13, 4], [16, 16, 16, 16]),
    ([440, 5, 17, 5], [440, 8, 24, 8]),
])
def test_append_cols_stick_to_the_small_append_the_window_ran(asks, cols):
    rt = _fresh_window()
    assert [rt.append_cols(k) for k in asks] == cols
