"""TPU query backend: routes supported rollups onto the device kernels
(the -search.tpuBackend analog).

try_rollup_tpu returns per-series rollup rows for ORACLE funcs, or None to
fall back to the host path. Series are packed into padded tiles; tiles are
cached in HBM keyed by the series-set fingerprint so repeated queries skip
the transfer (the reference's blockcache-hot behavior).
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from ..ops import rollup_np
from ..ops.rollup_np import RollupConfig
from ..utils import flightrec
from ..utils import metrics as metricslib

# (kernel, phase) -> histogram handle; keeps name formatting and the
# registry lock off the per-dispatch path (same memo pattern as rpc.py)
_kernel_hist_memo: dict = {}


def _kernel_histogram(kernel: str, phase: str):
    key = (kernel, phase)
    h = _kernel_hist_memo.get(key)
    if h is None:
        # benign double-create: REGISTRY.histogram dedups by name, so
        # two racing fills store the same object
        h = _kernel_hist_memo[key] = metricslib.REGISTRY.histogram(  # vmt: disable=VMT015
            metricslib.format_name("vm_tpu_kernel_duration_seconds",
                                   {"kernel": kernel, "phase": phase}))
    return h


def timed_kernel_call(kernel: str, jit_fn, *args, **kw):
    """Run a jitted kernel recording its wall time into
    vm_tpu_kernel_duration_seconds, split compile vs. execute: a call
    that grew the jit cache (jax's _cache_size) paid a trace+compile,
    everything else is pure dispatch/execute.  The split is the first
    thing to look at when p99 spikes — a 'compile' sample on a steady
    workload means a shape/dtype churned a cached kernel."""
    import jax
    cache_size = getattr(jit_fn, "_cache_size", None)
    before = cache_size() if callable(cache_size) else None
    # one phase from dispatch to ready: the flight event (arg = kernel),
    # the query's device:execute / device:compile cost bucket and query
    # phase counter, the profiler annotation — uploads are phased at the
    # put seams, this is the rest of the device leg
    with flightrec.phase("device:execute", arg=kernel) as ph:
        out = jit_fn(*args, **kw)
        # async dispatch returns immediately; without this sync the
        # phase would time dispatch overhead, not the kernel (callers
        # convert the result to numpy right after, so no extra blocking
        # is introduced)
        jax.block_until_ready(out)
        if before is not None and cache_size() > before:
            ph.name = "device:compile"
    _kernel_histogram(kernel, ph.name[len("device:"):]).update(ph.dur)
    return out


def _pull_host(out, dtype=np.float64) -> np.ndarray:
    """D2H pull of a kernel result with byte accounting + flight span —
    the one seam where device results cross back to the host."""
    from ..models.tile_cache import timed_transfer
    nbytes = int(np.prod(out.shape)) * np.dtype(dtype).itemsize
    return timed_transfer("device:download", nbytes,
                          lambda: np.asarray(out, dtype=dtype))

# -- the f32 tile design ------------------------------------------------
# Real TPUs have no native float64 (it is emulated, or silently truncated
# without x64), so device tiles there are float32 holding REBASED values
# v - v0, where v0 is the series' first uploaded value. The rebase happens
# in exact integer mantissa space on device (delta planes reconstruct from
# zero instead of the first mantissa), so a counter at 1e9 + small
# increments keeps FULL precision in its deltas — the one f32 rounding is
# the final scale multiply, bounding the error at ~2^-23 of the REBASED
# magnitude (window dynamic range), not of the absolute value.
#   F32_DIRECT funcs are shift-invariant (rate(v - v0) == rate(v)): they
#     run unchanged. Counter-reset classification needs the absolute base,
#     so kernels take v0 for the threshold compare (see
#     device_rollup._remove_counter_resets; post-reset precision degrades
#     to plain-f32 of the reset magnitude).
#   F32_AFFINE funcs satisfy f(v) = f(v - v0) + v0: the [S, T] device
#     output gets a host-side float64 addback per series (NaN gaps stay
#     NaN). Only valid where per-series outputs come back (not fused
#     cross-series aggregation, where group members have different v0).
#   Everything else (sum_over_time needs n*v0; cross-series selection on
#     absolute values) falls back to the f64 host path.
# The host evaluator stays float64 — the golden conformance corpus pins
# those numerics; tests/test_f32_tiles.py bounds device-vs-host error
# differentially. Precedent for lossy device numerics: the storage codec
# itself quantizes (lib/encoding/nearest_delta.go:15 precisionBits).
F32_DIRECT = frozenset({
    "count_over_time", "present_over_time", "stddev_over_time",
    "stdvar_over_time", "changes", "delta", "idelta", "increase",
    "increase_pure", "rate", "irate", "deriv", "deriv_fast", "lag",
    "lifetime", "scrape_interval", "timestamp", "tfirst_over_time",
    "tlast_over_time",
})
F32_AFFINE = frozenset({
    "min_over_time", "max_over_time", "avg_over_time", "first_over_time",
    "last_over_time", "default_rollup",
})


class V0Info:
    """Host-side companion of an f32 tile: per-series rebase offsets
    (float64 — the affine addback and append rebasing must not round
    through f32) plus the wide-range flag.

    `wide_range` is True when any series' REBASED magnitude |v - v0|
    reaches 2^24 (f32's exact-integer limit) — e.g. a large-base counter
    that resets mid-tile, or one that grows >16M within the window. The
    rebase guarantees nothing there: every value-dependent func would see
    ulp(|v - v0|)-sized noise, so they all fall back to the f64 host path
    for such tiles (per-series patching is possible future work).
    Value-free funcs (counts, timestamps) still run.

    `dev` is the mesh engine's f32 copy on the devices, placed by the rule
    table at the first fused query and kept with the tile through every
    append and slide (they hand this object on), so a warm query sends
    nothing per-series up."""

    __slots__ = ("offsets", "wide_range", "dev")

    def __init__(self, offsets: np.ndarray, wide_range: bool):
        self.offsets = offsets
        self.wide_range = wide_range
        self.dev = None

    def __getitem__(self, i):
        return self.offsets[i]


# funcs whose output never reads sample VALUES: immune to f32 value error
VALUE_FREE_FUNCS = frozenset({
    "count_over_time", "present_over_time", "lag", "lifetime",
    "scrape_interval", "timestamp", "tfirst_over_time", "tlast_over_time",
})
# rebased-magnitude bound above which f32 value math is unsafe
F32_SAFE_RANGE = float(1 << 24)


def init_backend():
    """Initialise JAX in THIS process, on whatever backend it gives under
    the environment the process was started with, and return its devices.
    A chip belongs to one process at a time: nothing probes it from a
    child first. Raises what JAX raises."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        # XLA-CPU f64 tiles silently truncate to f32 without x64; must be
        # set before the engine's first jax trace
        jax.config.update("jax_enable_x64", True)
    return devs


def auto_value_dtype():
    """float32 tiles on a TPU; float64 elsewhere (XLA-CPU has native f64 —
    the conformance dtype)."""
    import jax
    return np.float32 if jax.default_backend() == "tpu" else np.float64


_CACHE_DIR_SET = False
_COMPILE_EVENTS_SET = False
# REAL XLA backend compiles (the monitoring event fires only when XLA
# actually builds an executable — jit tracing-cache hits and cpp-fastpath
# misses that resolve in the Python cache do NOT tick this), and
# persistent-compile-cache hits (a warm process deserializes instead of
# compiling).  The fleet's ≤-compiles-per-bucket guard and the
# compile-cache smoke both read these; jit _cache_size growth is NOT a
# compile signal (donation/placement churn grows it without compiling).
_BACKEND_COMPILES = metricslib.REGISTRY.counter(
    "vm_device_backend_compiles_total")
_COMPILE_CACHE_HITS = metricslib.REGISTRY.counter(
    "vm_device_fleet_compile_cache_hits_total")
# fused aggr(rollup()) launches by the path that ran them: "mesh" is the
# series-sharded step (parallel/mesh.py), "single" the one-device kernel.
# On a host with several chips anything but 100 % mesh means a query fell
# off the sharded path.
_FUSED_LAUNCHES = {
    path: metricslib.REGISTRY.counter(metricslib.format_name(
        "vm_device_fused_launches_total", {"path": path}))
    for path in ("mesh", "single")}
# rows staged by a cold tile build, by the code that staged them: the
# native pass, its NumPy twin where the library is missing, or the dense
# tile where a row needs more than int32 (0 from import)
_TILE_BUILD_ROWS = {
    path: metricslib.REGISTRY.counter(metricslib.format_name(
        "vm_device_tile_build_rows_total", {"path": path}))
    for path in ("native", "python", "dense")}
# series-axis size of the newest engine's mesh (0 until an engine is built)
_SERIES_SHARDS = metricslib.REGISTRY.gauge("vm_device_series_shards")


def _register_compile_listeners():
    global _COMPILE_EVENTS_SET
    if _COMPILE_EVENTS_SET:
        return
    import threading

    from jax._src import monitoring  # no public seam for these events

    # backend_compile_duration fires on persistent-cache HITS too (the
    # event wraps compile-or-retrieve); the hit event precedes it in
    # the same call stack, so a thread-local pending flag swallows the
    # duration event a retrieval (not a real compile) produced.
    pending_hit = threading.local()

    def _on_dur(name, dur_s, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            if getattr(pending_hit, "n", 0) > 0:
                pending_hit.n -= 1
            else:
                _BACKEND_COMPILES.inc()

    def _on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            pending_hit.n = getattr(pending_hit, "n", 0) + 1
            _COMPILE_CACHE_HITS.inc()

    monitoring.register_event_duration_secs_listener(_on_dur)
    monitoring.register_event_listener(_on_event)
    _COMPILE_EVENTS_SET = True


def backend_compiles() -> int:
    """Count of REAL XLA compiles this process has paid so far."""
    return int(_BACKEND_COMPILES.get())


def compile_cache_hits() -> int:
    """Count of persistent-compile-cache hits (compiles NOT paid)."""
    return int(_COMPILE_CACHE_HITS.get())


# The one persistent compile cache when JAX_COMPILATION_CACHE_DIR is not
# set: a FIXED path in the checkout (git-ignored).  The path is part of
# jax's cache key, so a directory made from $HOME, a temp name, a pid or
# the time would never hit.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compilation_cache():
    """Turn on jax's persistent compilation cache so the fused-kernel
    compiles are paid once per machine, not once per process. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads it and no
    directory is set in code; otherwise COMPILE_CACHE_DIR. Idempotent."""
    global _CACHE_DIR_SET
    _register_compile_listeners()
    if _CACHE_DIR_SET:
        return
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    # fused rollup kernels are small but slow to compile: cache all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _CACHE_DIR_SET = True


@dataclasses.dataclass
class TPUEngine:
    cache_bytes: int = 2 << 30
    value_dtype: object = None  # None = auto (f32 on TPU, f64 elsewhere)
    min_series: int = 64        # below this the host path wins
    mesh: object = None         # jax.sharding.Mesh; series axis sharding
    last_roll_decline: str = ""  # why the last rolling advance fell back
    _cache: object = None
    _aux: object = None
    _wcache: object = None      # DeviceWindowCache (resident windows)
    _fleet: object = None       # query.fleet.FleetPlane (batched streams)

    def __post_init__(self):
        enable_compilation_cache()
        # every flightrec.phase shows on the profiler's host plane as
        # vm:<name>, on the device trace's clock (flightrec itself must
        # import without jax, so the engine hands it the annotation)
        import jax
        flightrec.set_annotator(jax.profiler.TraceAnnotation)
        if self.value_dtype is None:
            self.value_dtype = auto_value_dtype()
        _SERIES_SHARDS.set(self.series_shards())

    def is_f32(self) -> bool:
        return np.dtype(self.value_dtype) == np.float32

    def func_mode(self, func: str, per_series: bool):
        """How this engine's dtype can run `func`: "direct", "addback"
        (per-series host f64 + v0), or None (host fallback)."""
        if not self.is_f32():
            return "direct"
        if func in F32_DIRECT:
            return "direct"
        if per_series and func in F32_AFFINE:
            return "addback"
        return None

    def cache(self):
        if self._cache is None:
            from ..models.tile_cache import TileCache
            self._cache = TileCache(self.cache_bytes)
        return self._cache

    def window_cache(self):
        """Device-resident rolling windows (models.tile_cache
        .DeviceWindowCache): the state that makes a rolling refresh
        upload only its tail columns."""
        if self._wcache is None:
            from ..models.tile_cache import DeviceWindowCache
            self._wcache = DeviceWindowCache()
        return self._wcache

    def fleet(self):
        """Fleet-batched stream plane (query.fleet.FleetPlane): every
        device-resident matstream packed on one leading stream axis and
        served by ONE fused mesh launch per interval."""
        if self._fleet is None:
            from .fleet import FleetPlane
            self._fleet = FleetPlane(self)
        return self._fleet

    def series_shards(self) -> int:
        """Size of the mesh's series axis (1 = single-device engine)."""
        if self.mesh is None:
            return 1
        from ..parallel.mesh import AXIS_SERIES
        return self.mesh.shape[AXIS_SERIES]


def auto_mesh():
    """Series-axis mesh over every visible device, or None single-chip.
    The serving apps call this at startup: the same engine then answers
    identically on 1 chip and on a pod slice (the reference's
    vmselect-over-N-vmstorage scatter-gather, netstorage.go:374, becomes a
    mesh psum)."""
    import jax
    devs = jax.devices()
    if len(devs) < 2:
        return None
    from ..parallel.mesh import make_mesh
    return make_mesh(devs)


def _fingerprint(series, start_ms: int) -> tuple:
    import xxhash
    h = xxhash.xxh64()
    for sd in series:
        raw = getattr(sd, "raw_name", None)
        h.update(raw if raw is not None else sd.metric_name.marshal())
        h.update(np.int64(sd.timestamps.size).tobytes())
        if sd.timestamps.size:
            h.update(sd.timestamps[-1].tobytes())
    return ("tile", h.intdigest(), start_ms)


def try_rollup_tpu(engine: TPUEngine, func: str, series, cfg: RollupConfig,
                   args: tuple, cache_key=None):
    """Returns list of per-series value rows, or None for host fallback."""
    if func not in rollup_np.CORE_SUPPORTED:
        return None  # device kernels cover the core set; host batch the rest
    mode = engine.func_mode(func, per_series=True)
    if mode is None:
        return None  # f32 tiles cannot run this func; host f64 path
    if args:
        return None
    if len(series) < engine.min_series:
        return None
    span = cfg.end - cfg.start + cfg.lookback
    if span >= 2**31 - 1:
        return None  # needs chunking; host path handles it
    from ..ops.device_rollup import rollup_tile

    key = cache_key or _fingerprint(series, cfg.start)
    cache = engine.cache()
    tiles = cache.get(key)
    if tiles is None:
        tiles = _upload_tiles(engine, series, cfg)
        # retain the DECODED device tiles (not the planes): hot queries then
        # run straight on HBM-resident data
        cache.put_device(key, tiles)
    from ..ops.device_rollup import MIN_TS_NONE, normalized_cfg
    if _counter_unsafe(engine, func, tiles):
        return None
    ts_t, v_t, counts, v0 = tiles
    out = timed_kernel_call("rollup_tile", rollup_tile, func, ts_t, v_t,
                            counts, normalized_cfg(func, cfg), MIN_TS_NONE,
                            _v0_dev(engine, v0))
    # mesh tiles are row-padded; only the live rows come back
    rows = _pull_host(out)[:len(series)]
    if mode == "addback":
        rows = rows + v0[:len(series), None]  # NaN gaps stay NaN
    return list(rows)


TOPK_RANK_KINDS = frozenset({"max", "min", "avg", "median", "last"})


def try_topk_rollup_tpu(engine: TPUEngine, name: str, k: float, func: str,
                        series, cfg: RollupConfig, cache_key=None):
    """Fused topk/bottomk family on device: the [S, T] rollup stays in HBM;
    selection (per-timestamp top-k, or whole-series rank for the
    topk_<kind> variants) runs on device and only winner indices + the k
    selected rows cross the link (aggr.go:793 getRangeTopKTimeseries /
    topk per-ts).

    Returns a list of (orig_series_index, values_row) — the caller attaches
    names — or None for host fallback."""
    if func not in rollup_np.CORE_SUPPORTED:
        return None
    # selection compares values ACROSS series: rebased rows with different
    # v0 are not comparable, so f32 tiles only run shift-invariant funcs
    if engine.func_mode(func, per_series=False) != "direct":
        return None
    if len(series) < engine.min_series:
        return None
    span = cfg.end - cfg.start + cfg.lookback
    if span >= 2**31 - 1:
        return None
    bottom = name.startswith("bottomk")
    if name in ("topk", "bottomk"):
        kind = None
    else:
        kind = name.split("_", 1)[1]
        if kind not in TOPK_RANK_KINDS:
            return None
    import jax.numpy as jnp

    from ..ops.device_rollup import (normalized_cfg, rank_tile, take_rows,
                                     topk_select_tile)
    k_i = max(int(k), 0)
    if k_i == 0:
        return []
    key = cache_key or _fingerprint(series, cfg.start)
    cache = engine.cache()
    tiles = cache.get(key)
    if tiles is None:
        tiles = _upload_tiles(engine, series, cfg)
        cache.put_device(key, tiles)
    if _counter_unsafe(engine, func, tiles):
        return None
    ts_t, v_t, counts, v0 = tiles
    v0d = _v0_dev(engine, v0)
    ncfg = normalized_cfg(func, cfg)
    if kind is None:
        k_eff = min(k_i, int(ts_t.shape[0]))
        rolled, idx, sel_nan = timed_kernel_call(
            "topk_select_tile", topk_select_tile, func, ts_t, v_t, counts,
            ncfg, k_eff, bottom, v0=v0d)
        idx_h = np.asarray(idx)
        valid = ~np.asarray(sel_nan)
        # padded tile rows roll to all-NaN and can never be selected valid
        sel = np.unique(idx_h[valid])
        sel = sel[sel < len(series)]
        if sel.size == 0:
            return []
        rows_sel = _pull_host(take_rows(rolled, jnp.asarray(sel)))
        # rebuild the kept-sample mask for the selected rows
        t_pos, j_pos = np.nonzero(valid)
        s_pos = idx_h[t_pos, j_pos]
        keep = s_pos < len(series)
        row_of = np.searchsorted(sel, s_pos[keep])
        mask = np.zeros((sel.size, rows_sel.shape[1]), dtype=bool)
        mask[row_of, t_pos[keep]] = True
        out = []
        for j, i in enumerate(sel):
            vals = np.where(mask[j], rows_sel[j], np.nan)
            if not np.isnan(vals).all():
                out.append((int(i), vals))
        return out
    rolled, rank = timed_kernel_call("rank_tile", rank_tile, func, kind,
                                     ts_t, v_t, counts, ncfg, v0=v0d)
    rank_h = np.asarray(rank, dtype=np.float64)[:len(series)]
    # ordering replicates _eval_topk_family exactly (stable sorts, ties
    # favor later series)
    rank_h = np.where(np.isnan(rank_h),
                      np.inf if bottom else -np.inf, rank_h)
    if bottom:
        order = np.argsort(-rank_h, kind="stable")
    else:
        order = np.argsort(rank_h, kind="stable")
    sel = order[-min(k_i, len(series)):]  # rank order, ties favor later
    rows_sel = _pull_host(take_rows(rolled, jnp.asarray(sel)))
    return [(int(i), rows_sel[j]) for j, i in enumerate(sel)]


FUSED_AGGRS = frozenset({"sum", "count", "avg", "min", "max", "stddev",
                         "stdvar", "group"})


def try_aggr_rollup_tpu(engine: TPUEngine, aggr: str, func: str, series,
                        gids, num_groups: int, cfg: RollupConfig,
                        cache_key=None):
    """Fused aggr(rollup(selector)) on device: per-series rollup + segment
    aggregation run in one kernel, so only the [G, T] aggregate crosses the
    device->host link (the incrementalAggrFuncCallbacks analog,
    eval.go:1055).
    Returns an [G, T] float64 array or None for host fallback."""
    if aggr not in FUSED_AGGRS or func not in rollup_np.CORE_SUPPORTED:
        return None
    # group members have different v0, so f32 tiles only run
    # shift-invariant funcs fused (the affine addback is per-series)
    if engine.func_mode(func, per_series=False) != "direct":
        return None
    if len(series) < engine.min_series:
        return None
    span = cfg.end - cfg.start + cfg.lookback
    if span >= 2**31 - 1:
        return None
    key = cache_key or _fingerprint(series, cfg.start)
    cache = engine.cache()
    tiles = cache.get(key)
    if tiles is None:
        tiles = _upload_tiles(engine, series, cfg)
        cache.put_device(key, tiles)
    if _counter_unsafe(engine, func, tiles):
        return None
    return _dispatch_fused(engine, aggr, func, tiles,
                           place_series_vector(engine, "group_ids", gids),
                           num_groups, cfg)


def warmup(engine: TPUEngine, funcs=("rate", "increase", "default_rollup"),
           aggrs=("sum",)) -> int:
    """Pre-compile the hot fused/per-series kernels on a small canonical
    shape so the first real query pays neither jit-infrastructure init nor
    the kernel compile (which also seeds the persistent compilation cache,
    enable_compilation_cache). Serving apps call this at startup BEFORE
    the engine is attached; returns the number of kernels exercised.
    Raises what the device raises: a kernel the chip refuses must stop the
    server, not leave it serving from the host."""
    from ..storage.metric_name import MetricName
    from ..storage.storage import SeriesData
    from ..utils import fasttime
    n_runs = 0
    S, N = max(int(engine.min_series), 64), 128
    start = (fasttime.unix_ms() - N * 15_000) // 60_000 * 60_000
    rng = np.random.default_rng(7)
    series = []
    for i in range(S):
        ts = np.arange(N, dtype=np.int64) * 15_000 + start
        v = np.cumsum(rng.integers(0, 50, N)).astype(np.float64)
        mn = MetricName.from_dict({"__name__": "__warmup__", "i": str(i)})
        series.append(SeriesData(mn, ts, v, raw_name=mn.marshal()))
    cfg = RollupConfig(start=start + 600_000, end=start + (N - 1) * 15_000,
                       step=60_000, window=300_000)
    gids = np.zeros(S, np.int32)
    for func in funcs:
        if try_rollup_tpu(engine, func, series, cfg, ()) is not None:
            n_runs += 1
        for aggr in aggrs:
            if try_aggr_rollup_tpu(engine, aggr, func, series, gids, 1,
                                   cfg) is not None:
                n_runs += 1
    return n_runs


def _v0_dev(engine: TPUEngine, v0):
    """Rebase offsets in tile dtype for the kernel's counter-reset
    threshold (None for f64 engines — no rebase happened).  A mesh engine
    places them by the rule table once and keeps them on the tile's
    V0Info."""
    if v0 is None:
        return None
    if engine.series_shards() == 1:
        import jax.numpy as jnp
        return jnp.asarray(v0.offsets.astype(np.float32))
    if v0.dev is None:
        # once a tile: every later query, append and slide reuses it
        v0.dev = place_series_vector(engine, "v0",
                                     v0.offsets.astype(np.float32))
    return v0.dev


def _counter_unsafe(engine: TPUEngine, func: str, tiles) -> bool:
    """True when `func` reads sample values but this f32 tile's rebased
    dynamic range exceeds the f32-safe bound (see V0Info.wide_range)."""
    v0 = tiles[3]
    return v0 is not None and v0.wide_range and func not in VALUE_FREE_FUNCS


def place_series_vector(engine: TPUEngine, name: str, a: np.ndarray,
                        fill=0):
    """A per-series host vector (group ids, quantile slots) as the fused
    kernels take it, for whoever keeps it beside a resident tile.  On a
    mesh it is padded to the tile's rows (`fill` in the padding rows) and
    placed by the rule table HERE, once: every query then hands the placed
    array to the mesh step as it is, and nothing per-series crosses the
    boundary again."""
    if engine.series_shards() > 1:
        from ..parallel.partition import shard_put
        return shard_put(engine.mesh, name, a, fill)
    import jax.numpy as jnp
    return jnp.asarray(a)


def _pad_rows(arr, n_rows: int, fill):
    """Pad a [S]-vector to the tile's padded row count (mesh tiles round S
    up to a multiple of the series axis)."""
    import jax.numpy as jnp
    arr = jnp.asarray(arr)
    if arr.shape[0] >= n_rows:
        return arr
    pad = jnp.full((n_rows - arr.shape[0],), fill, dtype=arr.dtype)
    return jnp.concatenate([arr, pad])


def _dispatch_fused(engine: TPUEngine, aggr: str, func: str, tiles,
                    gids_dev, num_groups: int, cfg: RollupConfig,
                    shift: int = 0, min_ts=None):
    """Route a fused aggr(rollup()) to the single-device kernel or the
    mesh-sharded psum path (parallel/mesh.py). Padded rows carry count=0 so
    their rollup is NaN and contributes nothing to any group moment.
    `shift` rebases rolling-tile timestamps onto the query grid and
    `min_ts` reproduces fetch truncation on over-covering tiles (both
    traced, so rolling windows never recompile)."""
    from ..ops.device_rollup import (MIN_TS_NONE, normalized_cfg,
                                     rollup_aggregate_tile)
    if min_ts is None:
        min_ts = MIN_TS_NONE
    ts_t, v_t, counts, v0 = tiles
    gids_dev = _pad_rows(gids_dev, ts_t.shape[0], 0)
    cfg = normalized_cfg(func, cfg)
    if engine.series_shards() > 1:
        from ..parallel.mesh import cached_sharded_rollup_aggregate
        fn = cached_sharded_rollup_aggregate(engine.mesh, func, aggr, cfg,
                                             num_groups)
        _FUSED_LAUNCHES["mesh"].inc()
        out = timed_kernel_call("sharded_rollup_aggregate", fn, ts_t, v_t,
                                counts, gids_dev, np.int32(shift),
                                np.int32(min_ts), _v0_dev(engine, v0))
    else:
        _FUSED_LAUNCHES["single"].inc()
        out = timed_kernel_call("rollup_aggregate_tile",
                                rollup_aggregate_tile, func, aggr, ts_t,
                                v_t, counts, gids_dev, cfg, num_groups,
                                np.int32(shift), np.int32(min_ts),
                                _v0_dev(engine, v0))
    return _pull_host(out)


def _upload_tiles(engine: TPUEngine, series, cfg: RollupConfig):
    """Cold tile build + upload.  device:tile_build is the host staging
    (device_decode.stage_rows, padding); the puts nest inside
    it as device:upload phases and are charged there, not here."""
    with flightrec.phase("device:tile_build"):
        return _build_tiles(engine, series, cfg)


def _build_tiles(engine: TPUEngine, series, cfg: RollupConfig):
    """Cold upload: prefer compact delta planes decoded on device (~2-5
    B/sample over the link, SURVEY §7 'compressed columns cross the
    boundary'); fall back to dense tiles when the data needs >int32.

    With a multi-device mesh the rows (series axis) are padded to a multiple
    of the mesh's series axis and placed per the partition-rule table
    (parallel/partition.py) — the delta-plane decode is per-row, so under
    GSPMD each device decodes only its shard and the decoded tile never
    leaves its device (the scatter half of the reference's
    scatter-gather)."""
    import dataclasses
    import operator

    from ..ops import device_decode as dd
    from ..ops.device_rollup import TS_PAD, pack_series
    from ..models.tile_cache import chunked_device_put
    from ..parallel.partition import shard_put

    n_sh = engine.series_shards()

    def _put(a: np.ndarray, pad_value=0, name="ts"):
        if n_sh > 1:
            return shard_put(engine.mesh, name, a, pad_value)
        return chunked_device_put(np.asarray(a))

    f32 = engine.is_f32()
    ts_rows = list(map(operator.attrgetter("timestamps"), series))
    val_rows = list(map(operator.attrgetter("values"), series))
    # f32 tiles: per-series rebase offsets, float64, HOST-resident (the
    # affine addback and append-slice rebasing must not round through
    # f32), and the wide-range flag: the value-space gate, and the one on
    # the REBASED MANTISSA (the delta planes reconstruct m - m[0], then
    # scale: with fractional scales (10^-k) the mantissa range can exceed
    # 2^24 while the value-space gate passes, silently costing integer
    # exactness that equality-sensitive funcs (changes, reset
    # classification) need)
    staged = dd.stage_rows(ts_rows, val_rows, cfg.start, engine.value_dtype,
                           rebase=f32, gate=F32_SAFE_RANGE)
    planes, v0, risky = staged.planes, staged.v0, staged.risky
    _TILE_BUILD_ROWS[staged.path if planes is not None else "dense"].inc(
        len(series))
    if planes is not None:
        n = int(planes.counts.max())
        n_cap = tile_capacity(n)
        if n_cap > n:
            # headroom columns for rolling appends: zero d2 planes decode
            # into garbage tails that every kernel masks out via counts
            pad = max(n_cap - 2 - planes.ts_d2.shape[1], 0)
            planes = dataclasses.replace(
                planes,
                ts_d2=np.pad(planes.ts_d2, ((0, 0), (0, pad))),
                val_d2=np.pad(planes.val_d2, ((0, 0), (0, pad))))
        # padded rows get count=0 and scale=1: decode masks them to TS_PAD
        pad_vals = {"scale": 1}
        dev = [_put(getattr(planes, f.name), pad_vals.get(f.name, 0),
                    name=f.name)
               for f in dataclasses.fields(planes)]
        ts_t, v_t = dd.decode_tiles(*dev[:6], dev[6], dev[7], n_cap,
                                    engine.value_dtype, rebase=f32)
        return ts_t, v_t, dev[7], _pad_v0(v0, int(ts_t.shape[0]), risky)
    if f32:  # the dense tile holds v - v0 (v0: the first finite value)
        val_rows = np.split(staged.vals - np.repeat(v0, staged.counts),
                            np.cumsum(staged.counts)[:-1])
    ts, vals, counts = pack_series(
        list(zip(ts_rows, val_rows)), cfg.start,
        n_pad=tile_capacity(int(staged.counts.max(initial=1))),
        dtype=engine.value_dtype)
    ts_d = _put(ts, TS_PAD, name="ts")
    return (ts_d, _put(vals, name="values"), _put(counts, name="counts"),
            _pad_v0(v0, int(ts_d.shape[0]), risky))


def _pad_v0(v0, n_rows: int, risky):
    """Row-pad the host float64 rebase vector to the tile's padded row
    count and wrap it as V0Info (None passes through for f64 engines)."""
    if v0 is None:
        return None
    if v0.shape[0] < n_rows:
        v0 = np.concatenate([v0, np.zeros(n_rows - v0.shape[0])])
    return V0Info(v0, bool(risky))


def tile_capacity(n: int) -> int:
    """Column capacity for a freshly built tile: ~25% headroom (min 32
    columns) rounded to a multiple of 64, so rolling appends have room and
    rebuilt tiles land on few distinct compiled shapes."""
    return (max(n + 32, n * 5 // 4) + 63) // 64 * 64


class RollingTile:
    """An HBM-resident tile that advances with append-only ingest instead of
    rebuilding (the VERDICT-r2 'incremental tile maintenance': the
    reference's rollupResultCache reuses cached tails,
    rollup_result_cache.go:283 — here the TILE is the cache and new blocks
    append into reserved column headroom).

    Accuracy contract: the tail kernel's estimate-dependent prev-sample
    gating can drift vs a cold fresh-tile eval by up to ~one gated
    sample's increase per window under jittered scrape intervals
    (bounded in tests/test_served_device_path.py; the reference's cached
    columns drift the same way). Paths that need cold-exact results
    (the HTTP result cache's suffix eval) set EvalConfig.no_device_roll.

    Shared per selector across every fused query shape over it (sum/avg/...
    states reference the same RollingTile, so one append serves them all).
    The append DONATES the old device buffers; anything else holding them
    (the exact-key TileCache entry it was adopted from) must be invalidated
    first — advance_rolling() does that via `adopted_key`."""

    __slots__ = ("tiles", "base_ms", "n_cap", "lo_ms", "hi_ms", "version",
                 "structural", "counts_host", "row_of_raw", "n_samples",
                 "adopted_key", "appends", "segments")

    def __init__(self, tiles, base_ms, n_cap, lo_ms, hi_ms, version,
                 structural, counts_host, row_of_raw, n_samples,
                 adopted_key):
        self.tiles = tiles
        self.base_ms = base_ms
        self.n_cap = n_cap
        self.lo_ms = lo_ms
        self.hi_ms = hi_ms
        self.version = version
        self.structural = structural
        self.counts_host = counts_host
        self.row_of_raw = row_of_raw
        self.n_samples = n_samples
        self.adopted_key = adopted_key
        self.appends = 0
        # (seg_lo, seg_hi, n) per build/append: lets sample accounting for
        # -search.maxSamplesPerQuery charge only segments a query's fetch
        # range would actually touch, not the tile's whole history
        self.segments = [(lo_ms, hi_ms, n_samples)]

    def samples_in_range(self, fetch_lo: int) -> int:
        return sum(n for _, seg_hi, n in self.segments if seg_hi >= fetch_lo)


def advance_rolling(engine: TPUEngine, rt: RollingTile, storage, filters,
                    start: int, fetch_lo: int, end: int, max_series, tenant,
                    drop_stale: bool, tracer=None) -> bool:
    """Bring `rt` up to date with storage for a query fetching
    [fetch_lo, end]: fetch only the slice newer than the tile's covered
    range and append it on device. Returns False when the tile cannot be
    advanced (late/backfilled data, deletes, new series, capacity/int32
    exhausted) — the caller rebuilds via the cold path.

    Timed as device:tile_build: its self time is the host bookkeeping
    and staging around the slice fetch (fetch:wait), the window slide
    (device:execute) and the append's put (device:upload), which nest
    inside it and are charged to themselves."""
    with flightrec.phase("device:tile_build"):
        return _advance_rolling(engine, rt, storage, filters, start,
                                fetch_lo, end, max_series, tenant,
                                drop_stale, tracer)


def _advance_rolling(engine: TPUEngine, rt: RollingTile, storage, filters,
                     start: int, fetch_lo: int, end: int, max_series,
                     tenant, drop_stale: bool, tracer) -> bool:
    def no(reason: str) -> bool:
        engine.last_roll_decline = reason
        return False

    ver = getattr(storage, "data_version", None)
    if ver is None or \
            getattr(storage, "structural_version", None) != rt.structural:
        return no("deletes/retention changed visible data")
    if getattr(storage, "dedup_interval_ms", 0):
        return no("dedup interval set")  # buckets could straddle the append
    if rt.lo_ms > fetch_lo:
        return no("tile history does not reach this query's lookback")
    if start < rt.base_ms:
        # a negative shift would wrap the TS_PAD sentinel in int32 and
        # break row sortedness
        return no("query starts before the tile's rebase origin")
    if end - rt.base_ms >= 2**31 - 1:
        # window-slide compaction instead of a decline: drop samples
        # older than this query's fetch bound on device and move the
        # rebase origin there (compact_tile, donated) — the resident
        # window then rolls indefinitely instead of dying of int32
        if not compact_window(engine, rt, fetch_lo) or \
                end - rt.base_ms >= 2**31 - 1:
            return no("int32 rebase exhausted")
    if ver != rt.version:
        try:
            lo_new = storage.min_appended_since(rt.version)
        except LookupError:
            return no("append log trimmed past tile version")
        if lo_new is not None and lo_new <= rt.hi_ms:
            return no("late data landed inside the covered range")
    if end > rt.hi_ms:
        # extend coverage: anything in (hi, end] — new ingest OR data that
        # simply lay beyond the previous query's fetch bound — appends in
        # one slice fetch
        qt = tracer.new_child("slice fetch (%d, %d]", rt.hi_ms, end) \
            if tracer is not None else None
        try:
            cols = storage.search_columns(filters, rt.hi_ms + 1, end,
                                          max_series=max_series,
                                          tenant=tenant)
        except ResourceWarning as e:
            from .limits import QueryLimitError
            raise QueryLimitError(
                f"{e}; either narrow the selector or raise "
                f"-search.maxUniqueTimeseries") from None
        if getattr(storage, "last_partial", False):
            return no("partial slice fetch")
        if drop_stale:
            cols.drop_stale_nans()
        if qt is not None:
            qt.donef("%d series, %d samples", cols.n_series, cols.n_samples)
        if cols.n_series:
            qa = tracer.new_child("device append") if tracer is not None \
                else None
            ok = _append_cols(engine, rt, cols, fetch_lo)
            if qa is not None:
                qa.donef("%d samples -> row tails", cols.n_samples)
            if not ok:
                return no(engine.last_roll_decline)
            rt.segments.append((rt.hi_ms + 1, end, cols.n_samples))
        rt.hi_ms = end
    rt.version = ver
    return True


def compact_window(engine: TPUEngine, rt: RollingTile,
                   cutoff_abs: int) -> bool:
    """Slide the resident window on device: drop every sample older than
    `cutoff_abs` (this query's fetch lower bound — nothing at or past it
    can contribute to this or any later rolling query) and rebase the
    tile origin there, freeing column headroom and int32 range WITHOUT a
    re-upload (ops.device_rollup.compact_tile, donated buffers).  Queries
    reaching further back than the new origin decline via rt.lo_ms and
    rebuild — the loud fallback.  Returns False when nothing would move
    (cutoff at/behind the current origin)."""
    cutoff_rel = cutoff_abs - rt.base_ms
    if cutoff_rel <= 0 or cutoff_rel >= 2**31 - 1:
        # nothing to drop, or the tile is so stale (paused dashboard
        # resumed much later) that even the cutoff overflows the int32
        # frame: decline BEFORE mutating any state — np.int32() below
        # would raise OverflowError instead of the loud rebuild
        return False
    from ..models.tile_cache import count_window_compaction
    from ..ops.device_rollup import compact_tile
    # the old buffers are donated: drop the TileCache reference first so
    # no reachable entry keeps deleted arrays
    if rt.adopted_key is not None:
        engine.cache().invalidate(rt.adopted_key)
        rt.adopted_key = None
    ts_t, v_t, counts_t, v0 = rt.tiles
    new_ts, new_vals, new_counts = timed_kernel_call(
        "compact_tile", compact_tile, ts_t, v_t, counts_t,
        np.int32(cutoff_rel), np.int32(cutoff_rel))
    counts_host = np.asarray(new_counts).astype(np.int64)
    rt.tiles = (new_ts, new_vals, new_counts, v0)
    rt.counts_host = counts_host
    rt.n_samples = int(counts_host.sum())
    rt.base_ms = cutoff_abs
    rt.lo_ms = max(rt.lo_ms, cutoff_abs)
    # clamp the sample-accounting segments to the new history start;
    # partially clipped segments keep their full n (a conservative
    # overcount for -search.maxSamplesPerQuery accounting)
    rt.segments = [(max(lo, cutoff_abs), hi, n)
                   for lo, hi, n in rt.segments if hi >= cutoff_abs]
    count_window_compaction()
    return True


def _append_cols(engine: TPUEngine, rt: RollingTile, cols,
                 fetch_lo: int) -> bool:
    """Scatter a fetched slice (ColumnarSeries) onto the tile tails."""
    from ..ops.device_rollup import append_tile
    rows_idx = np.empty(cols.n_series, dtype=np.int64)
    for i, rn in enumerate(cols.raw_names):
        r = rt.row_of_raw.get(rn)
        if r is None:
            engine.last_roll_decline = "new series appeared"
            return False
        rows_idx[i] = r
    new_n = rt.counts_host[rows_idx] + cols.counts
    if int(new_n.max()) > rt.n_cap:
        # window-slide compaction before giving up: free the columns
        # holding samples older than this query's fetch bound
        if not compact_window(engine, rt, fetch_lo):
            engine.last_roll_decline = "column headroom exhausted"
            return False
        new_n = rt.counts_host[rows_idx] + cols.counts
        if int(new_n.max()) > rt.n_cap:
            engine.last_roll_decline = "column headroom exhausted"
            return False
    ts_t0, v_t0, counts_t0, v0 = rt.tiles
    S_tile = int(ts_t0.shape[0])
    K = int(cols.ts.shape[1])
    K_pad = (K + 7) // 8 * 8  # few distinct compiled append shapes
    new_ts = np.zeros((S_tile, K_pad), dtype=np.int32)
    new_vals = np.zeros((S_tile, K_pad), dtype=np.float64)
    new_counts = np.zeros(S_tile, dtype=np.int32)
    new_ts[rows_idx, :K] = (cols.ts - rt.base_ms).astype(np.int32)
    vals_in = cols.vals
    if v0 is not None:
        # f32 tiles hold rebased values: rebase the appended slice by the
        # SAME per-row offsets (f64 host subtraction, one f32 rounding).
        # An append pushing the rebased magnitude past the f32-safe range
        # (large-base counter reset, or >16M of growth) declines — the
        # caller rebuilds and the cold path re-gates via V0Info.
        vals_in = vals_in - v0[rows_idx][:, None]
        live = np.arange(K)[None, :] < cols.counts[:, None]
        sub = vals_in[live]  # padding rebases to -v0; exclude it
        finite = sub[np.isfinite(sub)]
        if not v0.wide_range and finite.size and \
                float(np.abs(finite).max()) >= F32_SAFE_RANGE:
            engine.last_roll_decline = \
                "append exceeds the f32-safe rebased range"
            return False
    new_vals[rows_idx, :K] = vals_in
    new_counts[rows_idx] = cols.counts
    # the old buffers are donated: drop the TileCache reference first so no
    # reachable entry keeps deleted arrays
    if rt.adopted_key is not None:
        engine.cache().invalidate(rt.adopted_key)
        rt.adopted_key = None
    ts_t, v_t, counts_t = ts_t0, v_t0, counts_t0
    if engine.series_shards() > 1:
        # the same body with the tail's shardings declared from the rule
        # table: each device is sent the staged rows it holds, no more
        from ..parallel.mesh import cached_sharded_append_tile
        append = cached_sharded_append_tile(engine.mesh)
    else:
        append = append_tile
    # the staged NumPy tail rides the jitted call's arguments, so that call
    # IS the put (async: it returns once the transfer and the kernel are
    # issued, it does not wait for them)
    from ..models.tile_cache import timed_transfer
    rt.tiles = timed_transfer(
        "device:upload",
        new_ts.nbytes + new_vals.nbytes + new_counts.nbytes,
        lambda: append(ts_t, v_t, counts_t, new_ts, new_vals,
                       new_counts)) + (v0,)
    rt.counts_host[rows_idx] = new_n
    rt.n_samples += cols.n_samples
    rt.appends += 1
    return True


def aux_cache(engine: TPUEngine):
    """Host-side LRU mapping a query-shape key to (tile_key, adjusted cfg,
    device gids, group keys, sample count): lets a warm fused query skip the
    host fetch entirely and go straight to the resident tile."""
    if engine._aux is None:
        from collections import OrderedDict
        engine._aux = OrderedDict()
    return engine._aux


def aux_get(engine: TPUEngine, key):
    aux = aux_cache(engine)
    hit = aux.get(key)
    if hit is not None:
        aux.move_to_end(key)  # true LRU: hits refresh recency
    return hit


def aux_put(engine: TPUEngine, key, value, cap: int = 1024):
    aux = aux_cache(engine)
    aux[key] = value
    aux.move_to_end(key)
    while len(aux) > cap:
        aux.popitem(last=False)


def run_fused_on_tiles(engine: TPUEngine, aggr: str, func: str, tiles,
                       gids_dev, num_groups: int, cfg: RollupConfig,
                       shift: int = 0, min_ts=None):
    """Fused kernel over an HBM-resident tile (warm-path shortcut: no host
    fetch, no upload)."""
    return _dispatch_fused(engine, aggr, func, tiles, gids_dev, num_groups,
                           cfg, shift, min_ts)


# HBM budget for the dense [G, M, T] quantile tensor. The kernel holds the
# scatter target AND its sorted copy simultaneously, so the element cap is
# budget / (itemsize * 2).
_QUANTILE_DENSE_BYTES = 512 << 20


def group_slots(gids, num_groups: int):
    """Per-series slot within its group + the largest group size — the ONE
    place this ordering is defined (warm-path reuse depends on it matching
    the cold-path scatter exactly)."""
    counts_per_group = np.bincount(gids, minlength=num_groups)
    max_group = int(counts_per_group.max()) if num_groups else 0
    next_slot = np.zeros(num_groups, dtype=np.int32)
    slots = np.empty(len(gids), dtype=np.int32)
    for i, g in enumerate(gids):
        slots[i] = next_slot[g]
        next_slot[g] += 1
    return slots, max_group


def quantile_dense_fits(engine: TPUEngine, num_groups: int, max_group: int,
                        cfg: RollupConfig) -> bool:
    T = (cfg.end - cfg.start) // cfg.step + 1
    itemsize = np.dtype(engine.value_dtype).itemsize
    return num_groups * max_group * T <= \
        _QUANTILE_DENSE_BYTES // (itemsize * 2)


def try_quantile_rollup_tpu(engine: TPUEngine, phi: float, func: str,
                            series, gids, num_groups: int,
                            cfg: RollupConfig, slots, max_group: int,
                            cache_key=None):
    """Fused quantile/median(phi, rollup(selector)) by (...) on device.
    `slots`/`max_group` come from group_slots(). Returns [G, T] float64 or
    None for host fallback."""
    if func not in rollup_np.CORE_SUPPORTED:
        return None
    # the quantile interpolates ACROSS group members (different v0)
    if engine.func_mode(func, per_series=False) != "direct":
        return None
    if len(series) < engine.min_series:
        return None
    span = cfg.end - cfg.start + cfg.lookback
    if span >= 2**31 - 1:
        return None
    if not quantile_dense_fits(engine, num_groups, max_group, cfg):
        return None  # skewed grouping: dense tensor too big, host wins
    key = cache_key or _fingerprint(series, cfg.start)
    cache = engine.cache()
    tiles = cache.get(key)
    if tiles is None:
        tiles = _upload_tiles(engine, series, cfg)
        cache.put_device(key, tiles)
    if _counter_unsafe(engine, func, tiles):
        return None
    return run_quantile_on_tiles(
        engine, phi, func, tiles,
        place_series_vector(engine, "group_ids", gids, num_groups),
        place_series_vector(engine, "slots", slots, max_group),
        num_groups, max_group, cfg)


def run_quantile_on_tiles(engine: TPUEngine, phi: float, func: str, tiles,
                          gids_dev, slots_dev, num_groups: int,
                          max_group: int, cfg: RollupConfig,
                          shift: int = 0, min_ts=None):
    """Warm-path fused quantile over an HBM-resident tile. On a mesh the
    jitted kernel runs under GSPMD on the sharded tile; padded rows get
    out-of-bounds (group, slot) indices so their NaN rollup rows are DROPPED
    by the scatter instead of clobbering a live slot."""
    from ..ops.device_rollup import (MIN_TS_NONE, normalized_cfg,
                                     rollup_quantile_tile)
    if min_ts is None:
        min_ts = MIN_TS_NONE
    ts_t, v_t, counts, v0 = tiles
    gids_dev = _pad_rows(gids_dev, ts_t.shape[0], num_groups)
    slots_dev = _pad_rows(slots_dev, ts_t.shape[0], max_group)
    out = rollup_quantile_tile(func, phi, ts_t, v_t, counts, gids_dev,
                               slots_dev, normalized_cfg(func, cfg),
                               num_groups, max_group, np.int32(shift),
                               np.int32(min_ts), _v0_dev(engine, v0))
    return _pull_host(out)
