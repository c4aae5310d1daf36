"""TPU rollup kernels: windowed rollups over (series, sample) tiles.

This is the device half of the query engine's north-star hot loop (the
reference's rollupConfig.doInternal window walk, rollup.go:688-825, and the
unpack+merge workers around it). Instead of a per-series sliding-window scan
with index gathers, every windowed quantity is a masked reduction over the
sample axis, which XLA fuses into plain VPU work:

- ``_masked_window_reduce`` is the core: ONE ``lax.scan`` over chunks of
  the sample axis; each step compares a ``[S, chunk, 1]`` slice of the
  timestamps with the ``[T]`` output grid (``ts <= grid[t]``,
  ``ts <= grid[t] - lookback``), and every requested reduction
  (sum / max / min of an ``[S, N]`` plane, or a count) is one
  ``[S, chunk, T]`` compare-select-reduce added into its ``[S, T]`` carry.
  Cost O(S*N*T); no searchsorted, no prefix-sum or sparse-table tables.
- ``rollup_tile`` asks that pass for the window's sample-index bounds
  (``lo``, ``hi``) and the previous sample's timestamp, plus what the
  function needs (window sums and moments, min / max, first / last as
  min / max of monotone planes), and finishes on ``[S, T]`` blocks; the
  few row gathers left (``_gather``) read neighbours of ``lo`` / ``hi``.
- counter resets: prefix sum of negative jumps (removeCounterResets,
  rollup.go:921) over the whole row, before the pass.
- ``rollup_aggregate_tile`` / ``fleet_rollup_aggregate_*`` fuse the group
  reduction (a one-hot matmul for sums, segment min / max) behind it;
  ``append_tile`` / ``compact_tile`` maintain the resident window.

Inputs are padded ragged tiles:
  ts:     int32 [S, N]  sample timestamps, ms, relative to cfg.start,
                        padded with TS_PAD (must exceed any window bound)
  values: float  [S, N] padded with anything (masked via counts)
  counts: int32 [S]     valid samples per row

Empty windows produce NaN, matching the ops/rollup_np.py oracle, which this
module must agree with bit-for-bit up to float association order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .rollup_np import RollupConfig

TS_PAD = np.int32(2**31 - 1)

# Funcs whose output embeds absolute time: they read cfg.start and cannot
# run on a start-rebased grid.
TIME_VALUED_FUNCS = frozenset({"tfirst_over_time", "tlast_over_time",
                               "timestamp"})


def normalized_cfg(func: str, cfg: RollupConfig) -> RollupConfig:
    """Rebase the window grid to start=0 for kernel compilation: tile
    timestamps are already relative to cfg.start and the grid is relative,
    so two queries with the same span/step/window share one compiled
    executable. Without this every rolling dashboard refresh (start/end
    advance each time) would recompile — and would miss the mesh layer's
    memoized shard_map closures. Time-valued funcs keep the absolute cfg."""
    if func in TIME_VALUED_FUNCS or cfg.start == 0:
        return cfg
    return RollupConfig(start=0, end=cfg.end - cfg.start, step=cfg.step,
                        window=cfg.window)


def _valid_mask(counts: jnp.ndarray, n: int) -> jnp.ndarray:
    return jnp.arange(n, dtype=jnp.int32)[None, :] < counts[:, None]


# sample-axis chunk of _masked_window_reduce's scan
_BOUNDS_CHUNK = 256


def _gather(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Row-wise gather: x [S, N], idx [S, T] -> [S, T], idx clipped."""
    idx = jnp.clip(idx, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, idx, axis=1)


def _remove_counter_resets(v: jnp.ndarray, valid: jnp.ndarray,
                           v0=None) -> jnp.ndarray:
    """Monotonize counters: add back the lost base at each reset (prefix sum
    of negative jumps). Pad positions contribute nothing.

    `v0` is the per-series REBASE offset when the tile holds rebased values
    (f32 tiles store v - v[0]; see tpu_engine f32 design): the
    reset-vs-correction threshold and the restarted base are defined on
    ABSOLUTE values (rollup.go:921 compares against the previous absolute
    sample), so both re-add v0. Classification happens in tile dtype — data
    within one ulp of the 8x-drop boundary may classify differently from
    the f64 host path (documented bound, tests/test_f32_tiles.py)."""
    vm = jnp.where(valid, v, 0.0)
    prev = jnp.concatenate([vm[:, :1], vm[:, :-1]], axis=1)
    pair_valid = valid & jnp.concatenate(
        [jnp.zeros_like(valid[:, :1]), valid[:, :-1]], axis=1)
    prev_abs = prev if v0 is None else prev + v0[:, None].astype(v.dtype)
    drop = jnp.where(pair_valid & (vm < prev),
                     jnp.where((prev - vm) * 8 < prev_abs, prev - vm,
                               prev_abs), 0.0)
    return v + jnp.cumsum(drop, axis=1)


def _max_prev_interval_tile(ts: jnp.ndarray, counts: jnp.ndarray,
                            cfg: RollupConfig, min_ts=None) -> jnp.ndarray:
    """Per-series maxPrevInterval [S], bit-compatible with
    rollup_np._max_prev_interval_for: 0.6 linear-interpolated quantile of the
    last <=20 sample intervals, inflated by the rollup.go:899 jitter table.
    Instant grids (start == end) use the step directly. Samples older than
    `min_ts` are excluded like the host's truncated fetch would."""
    S, N = ts.shape
    step = jnp.asarray(cfg.step, jnp.int32)
    if cfg.start >= cfg.end:
        return jnp.full((S,), step, dtype=jnp.int32)
    c = counts.astype(jnp.int32)
    base = jnp.clip(c - 21, 0, None)
    idx = base[:, None] + jnp.arange(21, dtype=jnp.int32)[None, :]
    tv = jnp.take_along_axis(ts, jnp.clip(idx, 0, N - 1), axis=1)
    valid = idx < c[:, None]
    if min_ts is not None:
        valid = valid & (tv >= jnp.int32(min_ts))
    # float32 is exact for interval magnitudes up to 2^24 ms (~4.6h) and
    # avoids the x64-truncation warning when jax_enable_x64 is off
    d = (tv[:, 1:] - tv[:, :-1]).astype(jnp.float32)
    dvalid = valid[:, 1:] & valid[:, :-1]
    n = dvalid.sum(axis=1)
    dsort = jnp.sort(jnp.where(dvalid, d, jnp.inf), axis=1)
    rank = (0.6 * jnp.maximum(n - 1, 0)).astype(jnp.float32)
    lo_i = jnp.floor(rank).astype(jnp.int32)
    hi_i = jnp.ceil(rank).astype(jnp.int32)
    v_lo = jnp.take_along_axis(dsort, lo_i[:, None], axis=1)[:, 0]
    v_hi = jnp.take_along_axis(dsort, hi_i[:, None], axis=1)[:, 0]
    q = v_lo + (rank - lo_i) * (v_hi - v_lo)
    # zero out the no-interval case BEFORE the int cast: inf -> int32
    # saturates to INT_MAX, which would sneak past the positivity guard
    si = jnp.where(n >= 1, q, 0.0).astype(jnp.int32)
    si = jnp.where(si > 0, si, step)
    mpi = jnp.select(
        [si <= 2_000, si <= 4_000, si <= 8_000, si <= 16_000, si <= 32_000],
        [si + 4 * si, si + 2 * si, si + si, si + si // 2, si + si // 4],
        si + si // 8)
    return mpi


MIN_TS_NONE = np.int32(-2**31 + 1)

_I32_MIN = np.int32(-2**31)
_I32_MAX = np.int32(2**31 - 1)


def _masked_window_reduce(ts: jnp.ndarray, cfg: RollupConfig, specs):
    """ONE fused pass over sample chunks computing several masked
    reductions at once — the TPU-shaped core of the windowed rollups.

    Windowed quantities that classically need per-(step) index gathers
    become masked reductions over the sample axis: gathers lower to slow
    scalar loads on TPU, while a [S, chunk, T] compare+select+reduce fuses
    into pure VPU work (measured ~25ms/gather vs ~5ms for a whole fused
    pass at 8192x1984x355). Monotone quantities (sorted timestamps,
    reset-corrected counters) make first/last/prev exact min/max.

    specs: list of (arr [S,N] | None, kind, op):
      arr None reduces a constant 1 (int32 counting)
      kind 'le_hi': mask ts <= grid[t]
           'le_lo': mask ts <= grid[t] - lookback
           'win'  : grid[t]-lookback < ts <= grid[t]
      op 'sum' | 'max' | 'min'
    Returns (results [S,T] list, grid). Padded samples carry ts == TS_PAD
    and are never selected by any mask.
    """
    T = (cfg.end - cfg.start) // cfg.step + 1
    grid = jnp.arange(T, dtype=jnp.int32) * np.int32(cfg.step)
    lo_t = grid - np.int32(cfg.lookback)
    S, N = ts.shape
    ch = min(_BOUNDS_CHUNK, N)
    n_ch = (N + ch - 1) // ch
    padn = n_ch * ch - N

    def prep(a, fill):
        if padn:
            a = jnp.pad(a, ((0, 0), (0, padn)), constant_values=fill)
        return jnp.moveaxis(a.reshape(S, n_ch, ch), 1, 0)

    ts_ch = prep(ts, TS_PAD)
    xs = {"ts": ts_ch}
    # derive inits from ts so they inherit its sharding variance: a plain
    # jnp.full would be an axis-invariant constant, which shard_map rejects
    # as a scan carry whose output varies over the series axis
    vary0 = (ts[:, :1] * 0)  # int32 [S, 1] of zeros, varying like ts
    inits = []
    for i, (a, kind, op) in enumerate(specs):
        if a is not None:
            xs[f"a{i}"] = prep(a, 0)
            dt = a.dtype
        else:
            dt = jnp.int32
        if op == "sum":
            const = 0
        elif op == "max":
            const = _I32_MIN if dt == jnp.int32 else -jnp.inf
        else:
            const = _I32_MAX if dt == jnp.int32 else jnp.inf
        init = jnp.broadcast_to(vary0.astype(dt), (S, T)) + \
            jnp.asarray(const, dt)
        inits.append(init)

    def body(carry, x):
        tc = x["ts"][:, :, None]
        m_hi = tc <= grid[None, None, :]
        m_lo = tc <= lo_t[None, None, :]
        out = []
        for i, ((a, kind, op), acc) in enumerate(zip(specs, carry)):
            mask = m_hi if kind == "le_hi" else (
                m_lo if kind == "le_lo" else m_hi & ~m_lo)
            if a is None:
                arr = jnp.ones((1, 1, 1), jnp.int32)
            else:
                arr = x[f"a{i}"][:, :, None]
            if op == "sum":
                r = jnp.sum(jnp.where(mask, arr, jnp.zeros((), acc.dtype)),
                            axis=1, dtype=acc.dtype)
                out.append(acc + r)
            elif op == "max":
                fill = _I32_MIN if acc.dtype == jnp.int32 else -jnp.inf
                r = jnp.max(jnp.where(mask, arr, fill), axis=1)
                out.append(jnp.maximum(acc, r))
            else:
                fill = _I32_MAX if acc.dtype == jnp.int32 else jnp.inf
                r = jnp.min(jnp.where(mask, arr, fill), axis=1)
                out.append(jnp.minimum(acc, r))
        return out, None

    res, _ = jax.lax.scan(body, inits, xs)
    return res, grid


@functools.partial(jax.jit, static_argnames=("func", "cfg"))
def rollup_tile(func: str, ts: jnp.ndarray, values: jnp.ndarray,
                counts: jnp.ndarray, cfg: RollupConfig,
                min_ts=MIN_TS_NONE, v0=None) -> jnp.ndarray:
    """Windowed rollup over a padded tile -> [S, T] float array (NaN = gap).

    `min_ts` (traced) reproduces the evaluator's fetch truncation on tiles
    that hold MORE history than the query would fetch (rolling tiles):
    samples older than min_ts never seed prevValue / boundary transitions,
    exactly as if the fetch had started there. Window samples themselves
    are always newer than any fetch bound, so only prev-sample accesses are
    gated."""
    S, N = ts.shape
    dtype = values.dtype
    nan = jnp.asarray(jnp.nan, dtype)
    valid = _valid_mask(counts, N)
    vm = jnp.where(valid, values, 0.0)
    tsf = jnp.where(valid, ts, 0).astype(dtype)

    # Fused masked-reduction plan: every func reduces lo/hi counts and the
    # prev-sample timestamp in ONE chunked pass; func-specific quantities
    # ride the same pass. Monotone quantities (sorted ts, reset-corrected
    # counters) turn first/last/prev gathers into exact min/max reductions.
    specs = [(None, "le_lo", "sum"), (None, "le_hi", "sum"),
             (ts, "le_lo", "max")]

    def run(extra):
        res, grid = _masked_window_reduce(ts, cfg, specs + extra)
        return res[0], res[1], res[2], res[3:], grid

    def finish(lo, hi, t_prev_i):
        n_win = (hi - lo).astype(dtype)
        have = hi > lo
        has_prev = (lo >= 1) & (t_prev_i >= jnp.int32(min_ts))
        return n_win, have, has_prev

    if func in ("count_over_time", "present_over_time"):
        lo, hi, t_prev_i, _, grid = run([])
        n_win, have, _ = finish(lo, hi, t_prev_i)
        out = n_win if func == "count_over_time" else jnp.ones_like(n_win)
        return jnp.where(have, out, nan)

    if func in ("sum_over_time", "avg_over_time"):
        lo, hi, t_prev_i, (s1,), grid = run([(vm, "win", "sum")])
        n_win, have, _ = finish(lo, hi, t_prev_i)
        out = s1 if func == "sum_over_time" else s1 / n_win
        return jnp.where(have, out, nan)
    if func in ("stddev_over_time", "stdvar_over_time"):
        # Center by the per-series mean first: variance is shift-invariant
        # and this keeps the E[x^2]-E[x]^2 cancellation well-conditioned.
        total = jnp.sum(vm, axis=1, keepdims=True)
        cnt_all = jnp.maximum(counts[:, None].astype(dtype), 1.0)
        centered = jnp.where(valid, values - total / cnt_all, 0.0)
        lo, hi, t_prev_i, (s1, s2), grid = run(
            [(centered, "win", "sum"), (centered * centered, "win", "sum")])
        n_win, have, _ = finish(lo, hi, t_prev_i)
        var = jnp.maximum(s2 / n_win - (s1 / n_win) ** 2, 0.0)
        return jnp.where(have,
                         jnp.sqrt(var) if func == "stddev_over_time" else var,
                         nan)
    if func in ("min_over_time", "max_over_time"):
        op = "min" if func == "min_over_time" else "max"
        lo, hi, t_prev_i, (m,), grid = run([(values, "win", op)])
        _, have, _ = finish(lo, hi, t_prev_i)
        return jnp.where(have, m, nan)

    # Timestamps in the tile are relative to cfg.start (int32 rebase);
    # t-valued funcs add the base back to return absolute unix seconds.
    base_s = jnp.asarray(cfg.start, dtype) / 1e3
    if func == "tfirst_over_time":
        lo, hi, t_prev_i, (tf,), grid = run([(ts, "win", "min")])
        _, have, _ = finish(lo, hi, t_prev_i)
        return jnp.where(have, tf.astype(dtype) / 1e3 + base_s, nan)
    if func in ("tlast_over_time", "timestamp", "lag"):
        lo, hi, t_prev_i, (tl,), grid = run([(ts, "le_hi", "max")])
        _, have, _ = finish(lo, hi, t_prev_i)
        tl = tl.astype(dtype)
        if func == "lag":
            return jnp.where(have,
                             (grid.astype(dtype)[None, :] - tl) / 1e3, nan)
        return jnp.where(have, tl / 1e3 + base_s, nan)

    if func == "first_over_time":
        lo, hi, t_prev_i, _, grid = run([])
        _, have, _ = finish(lo, hi, t_prev_i)
        return jnp.where(have, _gather(values, lo), nan)
    if func in ("last_over_time", "default_rollup"):
        lo, hi, t_prev_i, _, grid = run([])
        _, have, _ = finish(lo, hi, t_prev_i)
        return jnp.where(have, _gather(values, hi - 1), nan)

    if func == "changes":
        prev_col = jnp.concatenate([vm[:, :1], vm[:, :-1]], axis=1)
        pair_valid = valid & jnp.concatenate(
            [jnp.zeros_like(valid[:, :1]), valid[:, :-1]], axis=1)
        chg = jnp.where(pair_valid & (vm != prev_col), 1.0, 0.0)
        # chg[i] is the transition (i-1, i); the window sum already counts
        # the boundary transition from the real prev value. With no
        # (eligible) prev sample the first window sample is the baseline:
        # drop the boundary term.
        lo, hi, t_prev_i, (s,), grid = run([(chg, "win", "sum")])
        _, have, has_prev = finish(lo, hi, t_prev_i)
        boundary = _gather(chg, lo)
        return jnp.where(have, s - jnp.where(has_prev, 0.0, boundary), nan)

    if func == "delta":
        lo, hi, t_prev_i, _, grid = run([])
        _, have, has_prev = finish(lo, hi, t_prev_i)
        v_last = _gather(values, hi - 1)
        v_first = _gather(values, lo)
        # new-series baseline (rollup.go:2129, mirrors rollup_np): with no
        # sample before the window the counter is assumed born at 0 unless
        # its first value dwarfs the first in-window step. The compare and
        # the zero base live in ABSOLUTE values, so rebased tiles fold v0
        # back in (same precedent as _remove_counter_resets: the born case
        # only fires on small absolutes, so the f32 addback stays exact).
        v0c = jnp.zeros((), dtype) if v0 is None else \
            v0[:, None].astype(dtype)
        two = hi - lo >= 2
        d = jnp.where(two, _gather(values, lo + 1) - v_first,
                      jnp.zeros((), dtype))
        born = jnp.abs(v_first + v0c) < 10.0 * (jnp.abs(d) + 1.0)
        base = jnp.where(has_prev, _gather(values, lo - 1),
                         jnp.where(born, -v0c, v_first))
        return jnp.where(have, v_last - base, nan)
    if func == "idelta":
        lo, hi, t_prev_i, _, grid = run([])
        n_win, have, has_prev = finish(lo, hi, t_prev_i)
        mpi = _max_prev_interval_tile(ts, counts, cfg, min_ts)
        has_gprev = has_prev & (
            t_prev_i > (grid - cfg.lookback)[None, :] - mpi[:, None])
        two = hi - lo >= 2
        v_last = _gather(values, hi - 1)
        prev = jnp.where(two, _gather(values, hi - 2),
                         _gather(values, lo - 1))
        return jnp.where(have & (two | has_gprev), v_last - prev, nan)

    if func in ("increase", "increase_pure", "rate", "irate"):
        cv = _remove_counter_resets(values, valid, v0)
        # pads/invalid tails carry garbage values but ts == TS_PAD, so no
        # mask ever selects them; cv is non-decreasing on the valid prefix,
        # making last/first/prev exact max/min reductions (zero gathers)
        lo, hi, t_prev_i, red, grid = run([
            (cv, "le_hi", "max"),   # c_last
            (cv, "le_lo", "max"),   # c_prev
            (cv, "win", "min"),     # c_first
            (ts, "le_hi", "max"),   # t_last (int32)
            (ts, "win", "min"),     # t_first (int32)
        ])
        c_last, c_prev, c_first, t_last_i, t_first_i = red
        n_win, have, has_prev = finish(lo, hi, t_prev_i)
        if func in ("increase", "increase_pure"):
            # new-series baseline on the reset-corrected series (see the
            # delta branch above; increase_pure always counts from 0 —
            # rollup.go:2169)
            v0c = jnp.zeros((), dtype) if v0 is None else \
                v0[:, None].astype(dtype)
            if func == "increase_pure":
                nb = jnp.broadcast_to(-v0c, c_first.shape)
            else:
                two = hi - lo >= 2
                d = jnp.where(two, _gather(cv, lo + 1) - c_first,
                              jnp.zeros((), dtype))
                born = jnp.abs(c_first + v0c) < 10.0 * (jnp.abs(d) + 1.0)
                nb = jnp.where(born, -v0c, c_first)
            base = jnp.where(has_prev, c_prev, nb)
            return jnp.where(have, c_last - base, nan)
        # deriv-family prevValue gate (rollup.go:781): the sample before
        # the window seeds prevValue only within maxPrevInterval of the
        # window start
        mpi = _max_prev_interval_tile(ts, counts, cfg, min_ts)
        has_gprev = has_prev & (
            t_prev_i > (grid - cfg.lookback)[None, :] - mpi[:, None])
        t_last = t_last_i.astype(dtype)
        t_first = t_first_i.astype(dtype)
        t_prev = t_prev_i.astype(dtype)
        if func == "rate":
            two = hi - lo >= 2
            ok = have & (has_gprev | two)
            rate_base = jnp.where(has_gprev, c_prev, c_first)
            dt = jnp.where(has_gprev, t_last - t_prev,
                           t_last - t_first) / 1e3
            dv = c_last - rate_base
            return jnp.where(ok & (dt > 0), dv / dt, nan)
        # irate: last two samples
        two = hi - lo >= 2
        ok = have & (two | has_gprev)
        c_l2 = jnp.where(two, _gather(cv, hi - 2), c_prev)
        t_l2 = jnp.where(two, _gather(tsf, hi - 2), t_prev)
        dt = (t_last - t_l2) / 1e3
        return jnp.where(ok & (dt > 0), (c_last - c_l2) / dt, nan)

    if func == "deriv_fast":
        lo, hi, t_prev_i, (t_last_i,), grid = run([(ts, "le_hi", "max")])
        n_win, have, has_prev = finish(lo, hi, t_prev_i)
        mpi = _max_prev_interval_tile(ts, counts, cfg, min_ts)
        has_gprev = has_prev & (
            t_prev_i > (grid - cfg.lookback)[None, :] - mpi[:, None])
        v_last = _gather(values, hi - 1)
        t_last = t_last_i.astype(dtype)
        two = hi - lo >= 2
        base_v = jnp.where(has_gprev, _gather(values, lo - 1),
                           _gather(values, lo))
        base_t = jnp.where(has_gprev, _gather(tsf, lo - 1),
                           _gather(tsf, lo))
        ok = have & (has_gprev | two)
        dt = (t_last - base_t) / 1e3
        return jnp.where(ok & (dt > 0), (v_last - base_v) / dt, nan)

    if func == "deriv":
        # least-squares slope via masked moment sums, t in seconds shifted
        # to each window's first sample (subtracted analytically to keep
        # f32-path cancellation manageable)
        ts_s = jnp.where(valid, ts, 0).astype(dtype) / 1e3
        lo, hi, t_prev_i, red, grid = run([
            (jnp.where(valid, ts_s, 0.0), "win", "sum"),
            (jnp.where(valid, ts_s * ts_s, 0.0), "win", "sum"),
            (vm, "win", "sum"),
            (jnp.where(valid, ts_s * values, 0.0), "win", "sum"),
            (ts, "win", "min"),
        ])
        st, stt, sv, stv, t_first_i = red
        n_win, have, _ = finish(lo, hi, t_prev_i)
        t0 = t_first_i.astype(dtype) / 1e3
        # shift t -> t - t0: st' = st - n*t0; stt' = stt - 2 t0 st + n t0²;
        # stv' = stv - t0*sv
        st_ = st - n_win * t0
        stt_ = stt - 2 * t0 * st + n_win * t0 * t0
        stv_ = stv - t0 * sv
        den = n_win * stt_ - st_ * st_
        ok = have & (hi - lo >= 2)
        return jnp.where(ok & (den != 0),
                         (n_win * stv_ - st_ * sv) / den, nan)

    if func == "lifetime":
        lo, hi, t_prev_i, (t_last_i, t_first_i), grid = run(
            [(ts, "le_hi", "max"), (ts, "win", "min")])
        _, have, has_prev = finish(lo, hi, t_prev_i)
        t_last = t_last_i.astype(dtype)
        t_first = jnp.where(has_prev, tsf[:, :1],
                            t_first_i.astype(dtype))
        return jnp.where(have, (t_last - t_first) / 1e3, nan)
    if func == "scrape_interval":
        lo, hi, t_prev_i, (t_last_i, t_first_i), grid = run(
            [(ts, "le_hi", "max"), (ts, "win", "min")])
        n_win, have, has_prev = finish(lo, hi, t_prev_i)
        t_last = t_last_i.astype(dtype)
        t_first = t_first_i.astype(dtype)
        t_prev = t_prev_i.astype(dtype)
        two = hi - lo >= 2
        ok = have & (has_prev | two)
        dt = jnp.where(has_prev, t_last - t_prev, t_last - t_first) / 1e3
        cnt = jnp.where(has_prev, n_win, n_win - 1)
        return jnp.where(ok & (cnt > 0), dt / cnt, nan)

    raise ValueError(f"unsupported device rollup func {func!r}")


# ---------------------------------------------------------------------------
# Grouped aggregation over series (the incremental-aggregation analog:
# aggr_incremental.go:18-67 becomes one segment-reduction).
# ---------------------------------------------------------------------------

AGGR_FUNCS = ("sum", "count", "avg", "min", "max", "group", "stddev", "stdvar")


def _onehot_sum(onehot: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Group sums as [G, S] one-hot @ [S, T] on the MXU, at FULL f32
    precision: a TPU's default matmul precision rounds f32 operands to
    bf16 (one pass), which read 1.3e-3 relative on sum(rate) on a v5e
    against the 1e-5 bound (chip_smoke.py, PR 22). HIGHEST is the
    multi-pass bf16 decomposition; backends with native f32/f64 matmuls
    ignore it."""
    return jnp.matmul(onehot, x, precision=jax.lax.Precision.HIGHEST)


def partial_group_moments(aggr: str, rolled: jnp.ndarray,
                          group_ids: jnp.ndarray, num_groups: int
                          ) -> dict[str, tuple[jnp.ndarray, str]]:
    """Per-shard segment moments for one aggregate: {name: (array [G, T],
    cross-shard reduce kind 'sum'|'min'|'max')}. Splitting moments from
    finalization lets the mesh layer psum/pmin/pmax the moments across
    shards before finalizing — combining *finished* per-shard stats would be
    wrong for avg/stddev."""
    present = ~jnp.isnan(rolled)
    zeroed = jnp.where(present, rolled, 0.0)
    # group-sum as a one-hot matmul: [G, S] @ [S, T] runs on the MXU,
    # where segment_sum lowers to a serialized scatter-add on TPU. Gated:
    # the dense one-hot is O(G*S), so near-unique groupings (G ~ S) keep
    # the linear scatter; and a +-Inf value would leak NaN into OTHER
    # groups through 0*Inf, so those (rare) tiles take the scatter via cond.
    S = rolled.shape[0]
    use_matmul = num_groups * S <= (1 << 24)
    if use_matmul:
        onehot = (group_ids[None, :] ==
                  jnp.arange(num_groups, dtype=group_ids.dtype)[:, None]
                  ).astype(rolled.dtype)
        all_finite = jnp.all(jnp.isfinite(zeroed))

        def seg(x):
            return jax.lax.cond(
                all_finite,
                lambda y: _onehot_sum(onehot, y),
                lambda y: jax.ops.segment_sum(y, group_ids,
                                              num_segments=num_groups),
                x)

        cnt = _onehot_sum(onehot, present.astype(rolled.dtype))
    else:
        def seg(x):
            return jax.ops.segment_sum(x, group_ids,
                                       num_segments=num_groups)

        cnt = seg(present.astype(rolled.dtype))
    m = {"cnt": (cnt, "sum")}
    if aggr in ("sum", "avg", "stddev", "stdvar"):
        m["s1"] = (seg(zeroed), "sum")
    if aggr in ("stddev", "stdvar"):
        m["s2"] = (seg(zeroed * zeroed), "sum")
    if aggr == "min":
        m["min"] = (jax.ops.segment_min(jnp.where(present, rolled, jnp.inf),
                                        group_ids, num_segments=num_groups),
                    "min")
    if aggr == "max":
        m["max"] = (jax.ops.segment_max(jnp.where(present, rolled, -jnp.inf),
                                        group_ids, num_segments=num_groups),
                    "max")
    if aggr not in AGGR_FUNCS:
        raise ValueError(f"unsupported aggregate {aggr!r}")
    return m


def finalize_group_moments(aggr: str, m: dict[str, tuple[jnp.ndarray, str]]
                           ) -> jnp.ndarray:
    """Finalize (possibly cross-shard-reduced) moments into the [G, T]
    aggregate. Groups with no live series at a step yield NaN."""
    cnt = m["cnt"][0]
    nan = jnp.asarray(jnp.nan, cnt.dtype)
    if aggr == "sum":
        out = m["s1"][0]
    elif aggr == "avg":
        out = m["s1"][0] / cnt
    elif aggr in ("stddev", "stdvar"):
        mean = m["s1"][0] / cnt
        var = jnp.maximum(m["s2"][0] / cnt - mean * mean, 0.0)
        out = jnp.sqrt(var) if aggr == "stddev" else var
    elif aggr == "count":
        out = cnt
    elif aggr == "min":
        out = m["min"][0]
    elif aggr == "max":
        out = m["max"][0]
    elif aggr == "group":
        out = jnp.ones_like(cnt)
    else:
        raise ValueError(f"unsupported aggregate {aggr!r}")
    return jnp.where(cnt > 0, out, nan)


def aggregate_groups(aggr: str, rolled: jnp.ndarray, group_ids: jnp.ndarray,
                     num_groups: int) -> jnp.ndarray:
    """Aggregate per-series rollup results [S, T] into [G, T] by group id.
    NaN inputs mean 'series absent at this step' and are skipped."""
    return finalize_group_moments(
        aggr, partial_group_moments(aggr, rolled, group_ids, num_groups))


#: stream-axis aggregate selector for the fleet kernel: the aggregate is
#: a per-stream TRACED code, so streams mixing sum/max/count/... share
#: ONE compiled program per bucket shape instead of one per aggregate
FLEET_AGGR_CODES = {"sum": 0, "count": 1, "avg": 2, "min": 3, "max": 4,
                    "stddev": 5, "stdvar": 6, "group": 7}


def _fleet_group_aggregate(rolled: jnp.ndarray, group_ids: jnp.ndarray,
                           num_groups: int, aggr_code) -> jnp.ndarray:
    """All-moments segment aggregation + finalize-by-code: computes the
    same cnt/s1/s2/min/max moments partial_group_moments would (same ops,
    same order, so each selected aggregate matches the per-stream kernel
    at f64 resolution), finalizes every aggregate, and gathers the one
    `aggr_code` (traced int32) names."""
    present = ~jnp.isnan(rolled)
    zeroed = jnp.where(present, rolled, 0.0)
    S = rolled.shape[0]
    if num_groups * S <= (1 << 24):
        onehot = (group_ids[None, :] ==
                  jnp.arange(num_groups, dtype=group_ids.dtype)[:, None]
                  ).astype(rolled.dtype)
        all_finite = jnp.all(jnp.isfinite(zeroed))

        def seg(x):
            return jax.lax.cond(
                all_finite,
                lambda y: _onehot_sum(onehot, y),
                lambda y: jax.ops.segment_sum(y, group_ids,
                                              num_segments=num_groups),
                x)

        cnt = _onehot_sum(onehot, present.astype(rolled.dtype))
    else:
        def seg(x):
            return jax.ops.segment_sum(x, group_ids,
                                       num_segments=num_groups)

        cnt = seg(present.astype(rolled.dtype))
    s1 = seg(zeroed)
    s2 = seg(zeroed * zeroed)
    mn = jax.ops.segment_min(jnp.where(present, rolled, jnp.inf),
                             group_ids, num_segments=num_groups)
    mx = jax.ops.segment_max(jnp.where(present, rolled, -jnp.inf),
                             group_ids, num_segments=num_groups)
    mean = s1 / cnt
    var = jnp.maximum(s2 / cnt - mean * mean, 0.0)
    outs = jnp.stack([s1, cnt, mean, mn, mx, jnp.sqrt(var), var,
                      jnp.ones_like(cnt)])
    out = outs[aggr_code]
    nan = jnp.asarray(jnp.nan, cnt.dtype)
    return jnp.where(cnt > 0, out, nan)


def fleet_rollup_aggregate_impl(rollup_func: str, cfg: RollupConfig,
                                num_groups: int, fleet_ts: jnp.ndarray,
                                fleet_values: jnp.ndarray,
                                fleet_counts: jnp.ndarray,
                                fleet_gids: jnp.ndarray,
                                fleet_aggr: jnp.ndarray,
                                fleet_shift: jnp.ndarray,
                                fleet_min_ts: jnp.ndarray,
                                fleet_v0: jnp.ndarray) -> jnp.ndarray:
    """Fleet-batched aggr(rollup(m[d])) over [B, S, N] planes -> [B, G, T]:
    ONE program for every resident stream in a bucket.  Static per bucket:
    rollup_func, the normalized cfg grid, num_groups.  Per-stream traced:
    grid shift, fetch bound min_ts, aggregate code, rebase offsets —
    window masks per stream fall out of shift/min_ts exactly as in the
    per-stream rolling path (the bit-equality oracle).  Padded streams
    carry counts == 0 / ts == TS_PAD and roll up to all-NaN rows."""

    def one(ts, values, counts, gids, aggr_code, shift, min_ts, v0):
        rolled = rollup_tile(rollup_func, ts - jnp.int32(shift), values,
                             counts, cfg, min_ts, v0)
        return _fleet_group_aggregate(rolled, gids, num_groups, aggr_code)

    return jax.vmap(one)(fleet_ts, fleet_values, fleet_counts, fleet_gids,
                         fleet_aggr, fleet_shift, fleet_min_ts, fleet_v0)


@functools.partial(jax.jit,
                   static_argnames=("rollup_func", "cfg", "num_groups"))
def fleet_rollup_aggregate_tile(rollup_func: str, cfg: RollupConfig,
                                num_groups: int, fleet_ts, fleet_values,
                                fleet_counts, fleet_gids, fleet_aggr,
                                fleet_shift, fleet_min_ts, fleet_v0):
    """Single-device jit of fleet_rollup_aggregate_impl (mesh engines go
    through parallel.mesh.cached_fleet_rollup_aggregate instead)."""
    return fleet_rollup_aggregate_impl(rollup_func, cfg, num_groups,
                                       fleet_ts, fleet_values, fleet_counts,
                                       fleet_gids, fleet_aggr, fleet_shift,
                                       fleet_min_ts, fleet_v0)


@functools.partial(jax.jit, static_argnames=("rollup_func", "aggr", "cfg", "num_groups"))
def rollup_aggregate_tile(rollup_func: str, aggr: str, ts: jnp.ndarray,
                          values: jnp.ndarray, counts: jnp.ndarray,
                          group_ids: jnp.ndarray, cfg: RollupConfig,
                          num_groups: int, shift=0,
                          min_ts=MIN_TS_NONE, v0=None) -> jnp.ndarray:
    """Fused aggr(rollup(m[d])) over one tile -> [G, T].

    `shift` (traced int32, ms) rebases tile timestamps onto the cfg grid:
    rolling tiles keep timestamps relative to their original base while the
    query grid advances, so shift = query_start - tile_base. Time-valued
    funcs are not supported with shift != 0 (dispatch excludes them).
    `min_ts` is the query's fetch lower bound in the SHIFTED frame (see
    rollup_tile); `v0` the per-series rebase offsets of f32 tiles."""
    rolled = rollup_tile(rollup_func, ts - jnp.int32(shift), values, counts,
                         cfg, min_ts, v0)
    return aggregate_groups(aggr, rolled, group_ids, num_groups)


def _append_tile_body(ts: jnp.ndarray, values: jnp.ndarray,
                      counts: jnp.ndarray, new_ts: jnp.ndarray,
                      new_values: jnp.ndarray, new_counts: jnp.ndarray):
    S, N = ts.shape
    K = new_ts.shape[1]
    rows = jnp.arange(S, dtype=jnp.int32)[:, None]
    k = jnp.arange(K, dtype=jnp.int32)[None, :]
    live = k < new_counts[:, None]
    pos = jnp.where(live, counts.astype(jnp.int32)[:, None] + k, N)
    ts2 = ts.at[rows, pos].set(new_ts, mode="drop")
    v2 = values.at[rows, pos].set(new_values.astype(values.dtype),
                                  mode="drop")
    return ts2, v2, counts + new_counts.astype(counts.dtype)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def append_tile(ts: jnp.ndarray, values: jnp.ndarray, counts: jnp.ndarray,
                new_ts: jnp.ndarray, new_values: jnp.ndarray,
                new_counts: jnp.ndarray):
    """Rolling-tile advance: scatter newer samples onto each row's tail.

    The buffers are donated — the caller's old tile references become
    invalid and must be replaced with the returned arrays (this is what
    keeps the HBM-resident tile single-copy while ingest appends). New
    samples must be strictly newer than each row's existing samples (the
    eval layer guarantees this via the storage append watermark); per-row
    positions beyond new_counts[row] scatter out of bounds and are dropped."""
    return _append_tile_body(ts, values, counts, new_ts, new_values,
                             new_counts)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def fleet_append_tile(fleet_ts: jnp.ndarray, fleet_values: jnp.ndarray,
                      fleet_counts: jnp.ndarray, new_ts: jnp.ndarray,
                      new_values: jnp.ndarray, new_counts: jnp.ndarray):
    """Batched append over the fleet's leading stream axis: ONE donated
    launch scatters every staged stream's suffix columns [B, S, K] onto
    the packed [B, S, N] planes (query/fleet.py).  Streams with nothing
    staged carry new_counts == 0 rows and are untouched."""
    return jax.vmap(_append_tile_body)(fleet_ts, fleet_values, fleet_counts,
                                       new_ts, new_values, new_counts)


def _compact_tile_body(ts: jnp.ndarray, values: jnp.ndarray,
                       counts: jnp.ndarray, cutoff_rel, delta):
    S, N = ts.shape
    k = jnp.arange(N, dtype=jnp.int32)[None, :]
    valid = k < counts[:, None]
    drop = jnp.sum(valid & (ts < jnp.int32(cutoff_rel)), axis=1,
                   dtype=jnp.int32)
    new_counts = counts - drop
    idx = jnp.clip(drop[:, None] + k, 0, N - 1)
    live = k < new_counts[:, None]
    ts2 = jnp.where(live,
                    jnp.take_along_axis(ts, idx, axis=1) - jnp.int32(delta),
                    TS_PAD)
    v2 = jnp.where(live, jnp.take_along_axis(values, idx, axis=1),
                   jnp.zeros((), values.dtype))
    return ts2, v2, new_counts


@functools.partial(jax.jit, donate_argnums=(0, 1))
def compact_tile(ts: jnp.ndarray, values: jnp.ndarray, counts: jnp.ndarray,
                 cutoff_rel, delta):
    """Window-slide compaction of a rolling tile: drop each row's samples
    older than `cutoff_rel` (tile-relative ms, exclusive — samples AT the
    cutoff survive, matching the inclusive fetch lower bound), shift the
    survivors to the row front and rebase timestamps by `delta`
    (= new_base - old_base; both traced int32, so sliding windows never
    recompile).  The sample buffers are donated like append_tile's — the
    caller replaces its references with the returned arrays.  Freed tail
    positions are restored to TS_PAD so every kernel's masks stay valid.

    Correctness: rows are time-sorted, so dropped samples form a prefix.
    Samples older than the query fetch bound contribute nothing to any
    rollup (window masks exclude them; prev-sample accesses are gated by
    min_ts — see rollup_tile), so compacting at the CURRENT fetch_lo is
    invisible to this and every later query whose fetch bound is >= it;
    older-reaching queries decline via RollingTile.lo_ms and rebuild."""
    return _compact_tile_body(ts, values, counts, cutoff_rel, delta)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def fleet_compact_tile(fleet_ts: jnp.ndarray, fleet_values: jnp.ndarray,
                       fleet_counts: jnp.ndarray, cutoff_rel: jnp.ndarray,
                       delta: jnp.ndarray):
    """Batched window-slide compaction: per-stream cutoffs/deltas [B]
    (traced), one donated launch over the packed [B, S, N] planes.
    Streams with cutoff_rel <= 0 pass (cutoff 0, delta 0) and come back
    unchanged."""
    return jax.vmap(_compact_tile_body)(fleet_ts, fleet_values,
                                        fleet_counts, cutoff_rel, delta)


def pack_series(series: list[tuple[np.ndarray, np.ndarray]], start_ms: int,
                n_pad: int | None = None, dtype=np.float64
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side packing: ragged [(ts_ms, values)] -> padded tile arrays
    (ts_rel int32 [S, N], values [S, N], counts int32 [S]).

    Timestamps are re-based to start_ms so they fit int32 (range limit ~24.8
    days; the evaluator chunks longer ranges)."""
    S = len(series)
    counts = np.array([len(t) for t, _ in series], dtype=np.int32)
    N = n_pad or (int(counts.max()) if S else 1)
    N = max(N, 1)
    ts = np.full((S, N), TS_PAD, dtype=np.int32)
    vals = np.zeros((S, N), dtype=dtype)
    for i, (t, v) in enumerate(series):
        c = counts[i]
        rel = np.asarray(t, dtype=np.int64) - start_ms
        if c and (rel.max() >= TS_PAD or rel.min() <= -(2**31)):
            raise ValueError("time range too wide for int32 tile; chunk the query")
        ts[i, :c] = rel.astype(np.int32)
        vals[i, :c] = v
    return ts, vals, counts


@functools.partial(jax.jit, static_argnames=("func", "cfg", "k", "bottom"))
def topk_select_tile(func: str, ts: jnp.ndarray, values: jnp.ndarray,
                     counts: jnp.ndarray, cfg: RollupConfig, k: int,
                     bottom: bool, min_ts=MIN_TS_NONE, v0=None):
    """Per-timestamp topk/bottomk selection over a rolled tile: the [S, T]
    rollup never leaves the device — only [T, k] winner indices (+ NaN
    flags) cross the link, and the caller gathers just the selected rows
    (aggr.go topk/bottomk; host twin aggr_funcs.topk_mask_per_ts).
    Returns (rolled [device-resident], idx [T, k], sel_nan [T, k])."""
    rolled = rollup_tile(func, ts, values, counts, cfg, min_ts, v0)
    bad = jnp.isnan(rolled)
    key = jnp.where(bad, -jnp.inf, -rolled if bottom else rolled)
    _, idx = jax.lax.top_k(key.T, k)                   # [T, k]
    sel_nan = jnp.take_along_axis(bad.T, idx, axis=1)
    return rolled, idx, sel_nan


@functools.partial(jax.jit, static_argnames=("func", "kind", "cfg"))
def rank_tile(func: str, kind: str, ts: jnp.ndarray, values: jnp.ndarray,
              counts: jnp.ndarray, cfg: RollupConfig, min_ts=MIN_TS_NONE,
              v0=None):
    """topk_<kind>/bottomk_<kind> ranking: the whole-series statistic
    (aggr_funcs.series_rank_metric twin) computed on device — D2H is one
    float per series; the caller gathers only the k selected rows."""
    rolled = rollup_tile(func, ts, values, counts, cfg, min_ts, v0)
    bad = jnp.isnan(rolled)
    n = jnp.sum(~bad, axis=1)
    if kind == "max":
        r = jnp.max(jnp.where(bad, -jnp.inf, rolled), axis=1)
    elif kind == "min":
        r = jnp.min(jnp.where(bad, jnp.inf, rolled), axis=1)
    elif kind == "avg":
        r = jnp.sum(jnp.where(bad, 0.0, rolled), axis=1) / \
            jnp.maximum(n, 1).astype(rolled.dtype)
    elif kind == "median":
        sv = jnp.sort(jnp.where(bad, jnp.inf, rolled), axis=1)
        pos = 0.5 * jnp.maximum(n - 1, 0).astype(rolled.dtype)
        j0 = jnp.floor(pos).astype(jnp.int32)
        j1 = jnp.minimum(j0 + 1, jnp.maximum(n - 1, 0).astype(jnp.int32))
        a = jnp.take_along_axis(sv, j0[:, None], axis=1)[:, 0]
        b = jnp.take_along_axis(sv, j1[:, None], axis=1)[:, 0]
        r = a + (pos - j0.astype(rolled.dtype)) * (b - a)
    elif kind == "last":
        T = rolled.shape[1]
        j = T - 1 - jnp.argmax(jnp.flip(~bad, axis=1), axis=1)
        r = jnp.take_along_axis(rolled, j[:, None], axis=1)[:, 0]
    else:
        raise ValueError(f"unknown rank kind {kind!r}")
    nan = jnp.asarray(jnp.nan, rolled.dtype)
    return rolled, jnp.where(n == 0, nan, r)


@jax.jit
def take_rows(rolled: jnp.ndarray, sel: jnp.ndarray) -> jnp.ndarray:
    """Row gather on a device-resident rolled tile (the D2H tail of the
    topk kernels: only selected rows come back)."""
    return jnp.take(rolled, sel, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("rollup_func", "cfg", "num_groups",
                                    "max_group"))
def rollup_quantile_tile(rollup_func: str, phi, ts: jnp.ndarray,
                         values: jnp.ndarray, counts: jnp.ndarray,
                         group_ids: jnp.ndarray, slots: jnp.ndarray,
                         cfg: RollupConfig, num_groups: int,
                         max_group: int, shift=0,
                         min_ts=MIN_TS_NONE, v0=None) -> jnp.ndarray:
    """Fused quantile(phi, rollup(m[d])) by (...) -> [G, T].

    The per-series rollup [S, T] is scattered into a dense [G, M, T] tensor
    (M = largest group, host-precomputed per-series slot within its group),
    sorted along M (NaN gaps sort last), and linearly interpolated at
    phi*(n-1) per (group, step) — matching the host a_quantile /
    np.nanquantile semantics. The caller bounds G*M*T so skewed groupings
    fall back to the host path rather than exploding HBM."""
    rolled = rollup_tile(rollup_func, ts - jnp.int32(shift), values, counts,
                         cfg, min_ts, v0)  # [S, T]
    S, T = rolled.shape
    dtype = rolled.dtype
    nan = jnp.asarray(jnp.nan, dtype)
    dense = jnp.full((num_groups, max_group, T), nan, dtype)
    dense = dense.at[group_ids, slots].set(rolled)
    dsort = jnp.sort(dense, axis=1)  # NaNs last per (g, t)
    valid = ~jnp.isnan(rolled)
    n = jnp.zeros((num_groups, T), jnp.int32).at[group_ids].add(
        valid.astype(jnp.int32))  # live series per (g, t)
    phi_arr = jnp.asarray(phi, dtype)
    rank = jnp.clip(phi_arr, 0.0, 1.0) * jnp.maximum(n - 1, 0)
    lo = jnp.floor(rank).astype(jnp.int32)
    hi = jnp.ceil(rank).astype(jnp.int32)
    g_idx = jnp.arange(num_groups, dtype=jnp.int32)[:, None]
    t_idx = jnp.arange(T, dtype=jnp.int32)[None, :]
    v_lo = dsort[g_idx, lo, t_idx]
    v_hi = dsort[g_idx, hi, t_idx]
    q = v_lo + (rank - lo) * (v_hi - v_lo)
    # reference a_quantile: phi<0 -> -Inf, phi>1 -> +Inf on live steps
    q = jnp.where(phi_arr < 0, -jnp.inf, q)
    q = jnp.where(phi_arr > 1, jnp.inf, q)
    return jnp.where(n > 0, q, nan)
