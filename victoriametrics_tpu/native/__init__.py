"""ctypes bindings for the native host codec kernels (codec.cpp).

Auto-builds libvmcodec.so with g++ on first import if it is missing or
older than its sources (and a compiler is available); falls back to None
so callers keep their NumPy paths. This mirrors the reference's
cgo-zstd-with-pure-Go-fallback split
(lib/encoding/zstd/zstd_{cgo,pure}.go).
"""

from __future__ import annotations

import collections
import ctypes
import os
import subprocess
import sys

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libvmcodec.so")

_SOURCES = ("codec.cpp", "parse.cpp", "ingest.cpp", "format.cpp",
            "pending.cpp", "Makefile")

_lib = None


def _stale() -> bool:
    """The (git-ignored) library is missing or older than what it is
    built from."""
    try:
        built = os.path.getmtime(_SO)
    except OSError:
        return True
    return any(os.path.getmtime(os.path.join(_DIR, f)) > built
               for f in _SOURCES)


def _build() -> bool:
    try:
        subprocess.run(["make", "-C", _DIR, "-s"], check=True,
                       capture_output=True, timeout=120)
        return os.path.exists(_SO)
    except (subprocess.SubprocessError, FileNotFoundError):
        return False


def _load():
    global _lib
    if _lib is not None:
        return _lib
    if _stale() and not _build():
        return None
    try:
        lib = _configure(ctypes.CDLL(_SO))
    except OSError:
        return None
    except AttributeError:
        # stale .so from older sources (the binary is untracked): rebuild
        # once, then give up and let callers keep their numpy paths
        try:
            os.remove(_SO)
        except OSError:
            return None
        if not _build():
            return None
        try:
            lib = _configure(ctypes.CDLL(_SO))
        except (OSError, AttributeError):
            return None
    # benign double-load: racing loaders dlopen the same .so and store
    # equivalent handles; the loser's handle is dropped, never used half-set
    _lib = lib  # vmt: disable=VMT015
    return lib


def _configure(lib):
    i64 = ctypes.c_int64
    p8 = ctypes.POINTER(ctypes.c_uint8)
    pi64 = ctypes.POINTER(i64)
    lib.vm_varint_encode.restype = i64
    lib.vm_varint_encode.argtypes = [pi64, i64, p8]
    lib.vm_varint_decode.restype = i64
    lib.vm_varint_decode.argtypes = [p8, i64, pi64, i64]
    lib.vm_delta2_encode.restype = i64
    lib.vm_delta2_encode.argtypes = [pi64, i64, p8, pi64, pi64]
    lib.vm_delta2_decode.restype = i64
    lib.vm_delta2_decode.argtypes = [p8, i64, i64, i64, pi64, i64]
    lib.vm_delta_encode.restype = i64
    lib.vm_delta_encode.argtypes = [pi64, i64, p8, pi64]
    lib.vm_delta_decode.restype = i64
    lib.vm_delta_decode.argtypes = [p8, i64, i64, pi64, i64]
    pi32 = ctypes.POINTER(ctypes.c_int32)
    pf64 = ctypes.POINTER(ctypes.c_double)
    lib.vm_parse_prom.restype = i64
    lib.vm_parse_prom.argtypes = [ctypes.c_char_p, i64, pi32, pi32,
                                  pf64, pi64, i64]
    lib.vm_marshal_i64_many.restype = i64
    lib.vm_marshal_i64_many.argtypes = [pi64, pi64, i64, p8, i64,
                                        pi32, pi64, pi64]
    lib.vm_has_zstd.restype = ctypes.c_int32
    lib.vm_has_zstd.argtypes = []
    lib.vm_decompress_caps.restype = ctypes.c_int32
    lib.vm_decompress_caps.argtypes = []
    lib.vm_zstd_compress_bound.restype = i64
    lib.vm_zstd_compress_bound.argtypes = [i64]
    lib.vm_zstd_compress.restype = i64
    lib.vm_zstd_compress.argtypes = [p8, i64, p8, i64, ctypes.c_int32]
    lib.vm_zstd_content_size.restype = i64
    lib.vm_zstd_content_size.argtypes = [p8, i64]
    lib.vm_zstd_decompress.restype = i64
    lib.vm_zstd_decompress.argtypes = [p8, i64, p8, i64]
    lib.vm_assemble_part.restype = i64
    lib.vm_assemble_part.argtypes = [p8, p8, pi64, pi64, pi32, pi64,
                                     pi64, pi64, pi32, pi64, pi64, pi64,
                                     i64, i64, i64, pi64, pf64, pi64]
    lib.vm_dedup_rows.restype = None
    lib.vm_dedup_rows.argtypes = [pi64, i64, pf64, i64, pi64, pi64, i64,
                                  i64, i64]
    lib.vm_decode_blocks.restype = i64
    lib.vm_decode_blocks.argtypes = [p8, pi64, pi64, pi32, pi64, pi64,
                                     i64, pi64, ctypes.c_int32]
    lib.vm_decimal_to_float_blocks.restype = None
    lib.vm_decimal_to_float_blocks.argtypes = [pi64, pi64, pi64, i64, pf64]
    lib.vm_clip_blocks.restype = None
    lib.vm_clip_blocks.argtypes = [pi64, pi64, pi64, i64, i64, i64,
                                   pi64, pi64]
    lib.vm_gather_rows2.restype = None
    lib.vm_gather_rows2.argtypes = [pi64, pi64, pi64, pi64, i64, pi64, pi64]
    lib.vm_scatter_pad.restype = None
    lib.vm_scatter_pad.argtypes = [pi64, pf64, pi64, pi64, i64, i64, i64,
                                   i64, pi64, pf64, pi64]
    lib.vm_counter_resets_2d.restype = None
    lib.vm_counter_resets_2d.argtypes = [pf64, i64, i64, pf64]
    lib.vm_f2d_grouped.restype = None
    lib.vm_f2d_grouped.argtypes = [pf64, pi64, i64, i64, pi64, pi64]
    lib.vm_pack_delta_planes.restype = i64
    lib.vm_pack_delta_planes.argtypes = [pi64, pi64, pf64, pi64, i64, i64,
                                         i64, ctypes.c_int32, i64,
                                         ctypes.c_double, pi32, pi32, pi32,
                                         pi32, pi32, pi32, pi64, pi64, pf64,
                                         pi32]
    lib.vm_rollup_counter_2d.restype = None
    lib.vm_rollup_counter_2d.argtypes = [pi64, pf64, pi64, i64, i64, i64,
                                         i64, i64, i64, pi64,
                                         ctypes.c_int32, pf64, pf64]
    lib.vm_snappy_uncompressed_len.restype = i64
    lib.vm_snappy_uncompressed_len.argtypes = [p8, i64]
    lib.vm_snappy_uncompress.restype = i64
    lib.vm_snappy_uncompress.argtypes = [p8, i64, p8, i64]
    lib.vm_parse_rw.restype = i64
    lib.vm_parse_rw.argtypes = [p8, i64, i64, p8, i64, pi64, pi64,
                                pf64, pi64, i64]
    lib.vm_parse_influx.restype = i64
    lib.vm_parse_influx.argtypes = [p8, i64, p8, i64, i64, p8, i64,
                                    pi64, pi64, pf64, pi64, i64]
    lib.vm_keymap_new.restype = i64
    lib.vm_keymap_new.argtypes = []
    lib.vm_keymap_free.restype = None
    lib.vm_keymap_free.argtypes = [i64]
    lib.vm_keymap_size.restype = i64
    lib.vm_keymap_size.argtypes = [i64]
    lib.vm_keymap_resolve.restype = i64
    lib.vm_keymap_resolve.argtypes = [i64, p8, pi64, pi64, i64, pi64]
    lib.vm_write_matrix.restype = i64
    lib.vm_write_matrix.argtypes = [pf64, i64, pf64, i64, p8, i64, pi64,
                                    pi64, pi64, pi64]
    lib.vm_write_matrix_cut.restype = i64
    lib.vm_write_matrix_cut.argtypes = [pf64, i64, pf64, i64, p8, i64,
                                        pi64, pi64, pi64, i64]
    pp = ctypes.POINTER(ctypes.c_void_p)
    lib.vm_pending_order.restype = i64
    lib.vm_pending_order.argtypes = [pp, pp, pp, pi64, i64, pi32, i64, i64,
                                     ctypes.c_void_p, i64, pi64, pf64, pi64,
                                     ctypes.c_void_p, pi64]
    return lib


def available() -> bool:
    return _load() is not None


def has_zstd() -> bool:
    """True when zstd frames decode natively (linked libzstd or the
    runtime libzstd.so.1 resolved via dlopen); callers with zstd-marshaled
    blocks must otherwise take their Python path."""
    lib = _load()
    return bool(lib is not None and lib.vm_has_zstd())


def decompress_caps() -> int:
    """Bitmask of compressed-payload codecs the native decoder can
    inflate: bit 0 = zstd frames, bit 1 = zlib fallback streams."""
    lib = _load()
    return int(lib.vm_decompress_caps()) if lib is not None else 0


def assemble_enabled() -> bool:
    """Whether the fused native read kernel (vm_assemble_part) serves
    queries. ``VM_NATIVE_ASSEMBLE=0`` is the escape hatch AND the
    correctness oracle: it restores the split Python-orchestrated
    collect/decode/assemble path exactly. Re-read per call, like
    VM_SEARCH_WORKERS, so tests can flip modes without restarting."""
    return os.environ.get("VM_NATIVE_ASSEMBLE", "1") != "0" and available()


def zstd_compress(data: bytes, level: int = 1):
    """One-shot zstd compress via the runtime library; None when zstd is
    unavailable (callers fall back to zlib)."""
    lib = _load()
    if lib is None:
        return None
    cap = lib.vm_zstd_compress_bound(len(data))
    if cap < 0:
        return None
    out = ctypes.create_string_buffer(int(cap) or 1)
    n = lib.vm_zstd_compress(
        _as_u8_ptr(data), len(data),
        ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)), cap, level)
    if n < 0:
        return None
    return out.raw[:n]


def zstd_decompress(data: bytes, max_size: int = 1 << 30):
    """One-shot zstd decompress, allocation-bounded by the frame's claimed
    content size (refused when unknown or above max_size — a hostile frame
    cannot balloon memory). None when zstd is unavailable; raises on a
    corrupt/oversized frame."""
    lib = _load()
    if lib is None or not lib.vm_has_zstd():
        return None
    src = _as_u8_ptr(data)
    size = lib.vm_zstd_content_size(src, len(data))
    if size < 0 or size > max_size:
        raise ValueError(
            f"zstd frame claims unknown or oversized content ({size})")
    out = ctypes.create_string_buffer(int(size) or 1)
    n = lib.vm_zstd_decompress(
        src, len(data), ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)),
        size)
    if n != size:
        raise ValueError("native zstd: malformed frame")
    return out.raw[:n]


def _as_i64_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _as_u8_ptr(b):
    return ctypes.cast(ctypes.c_char_p(bytes(b) if not isinstance(b, (bytes, bytearray)) else b),
                       ctypes.POINTER(ctypes.c_uint8))


def varint_encode(vals: np.ndarray) -> bytes:
    lib = _load()
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    out = ctypes.create_string_buffer(int(vals.size) * 10 or 1)
    n = lib.vm_varint_encode(_as_i64_ptr(vals), vals.size,
                             ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)))
    return out.raw[:n]


def varint_decode(data: bytes, count: int) -> np.ndarray:
    lib = _load()
    out = np.empty(count, dtype=np.int64)
    n = lib.vm_varint_decode(_as_u8_ptr(data), len(data), _as_i64_ptr(out),
                             count)
    if n != count:
        raise ValueError(f"native varint: expected {count} values, got {n}")
    return out


def delta2_encode(vals: np.ndarray) -> tuple[bytes, int, int]:
    lib = _load()
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    out = ctypes.create_string_buffer(int(vals.size) * 10 or 1)
    first = ctypes.c_int64()
    fd = ctypes.c_int64()
    n = lib.vm_delta2_encode(_as_i64_ptr(vals), vals.size,
                             ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)),
                             ctypes.byref(first), ctypes.byref(fd))
    if n < 0:
        raise ValueError("native delta2 encode failed")
    return out.raw[:n], first.value, fd.value


def delta2_decode(data: bytes, first: int, first_delta: int,
                  count: int) -> np.ndarray:
    lib = _load()
    out = np.empty(count, dtype=np.int64)
    n = lib.vm_delta2_decode(_as_u8_ptr(data), len(data), first, first_delta,
                             _as_i64_ptr(out), count)
    if n != count:
        raise ValueError("native delta2: malformed payload")
    return out


def delta_encode(vals: np.ndarray) -> tuple[bytes, int]:
    lib = _load()
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    out = ctypes.create_string_buffer(int(vals.size) * 10 or 1)
    first = ctypes.c_int64()
    n = lib.vm_delta_encode(_as_i64_ptr(vals), vals.size,
                            ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)),
                            ctypes.byref(first))
    if n < 0:
        raise ValueError("native delta encode failed")
    return out.raw[:n], first.value


def delta_decode(data: bytes, first: int, count: int) -> np.ndarray:
    lib = _load()
    out = np.empty(count, dtype=np.int64)
    n = lib.vm_delta_decode(_as_u8_ptr(data), len(data), first,
                            _as_i64_ptr(out), count)
    if n != count:
        raise ValueError("native delta: malformed payload")
    return out


_TS_ABSENT = -(2 ** 63)  # INT64_MIN sentinel from vm_parse_prom


def parse_prom_raw(data: bytes, default_ts: int):
    """Native prometheus text parse -> list of (series_key_bytes, ts_ms,
    value). Returns None when the native library is unavailable (callers
    fall back to the Python parser). The series key is the raw
    `name{labels}` prefix — the storage TSID cache is keyed on it directly,
    so repeat scrapes never materialize labels."""
    lib = _load()
    if lib is None:
        return None
    n_max = data.count(b"\n") + 2
    key_off = np.empty(n_max, dtype=np.int32)
    key_len = np.empty(n_max, dtype=np.int32)
    values = np.empty(n_max, dtype=np.float64)
    tss = np.empty(n_max, dtype=np.int64)
    n = lib.vm_parse_prom(
        data, len(data),
        key_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        key_len.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i64_ptr(tss), n_max)
    out = []
    mv = memoryview(data)
    for i in range(n):
        o = key_off[i]
        ts = tss[i]
        # explicit 0 is "no timestamp" too, matching the Python ingest path
        # (Row.with_default_ts treats 0 as absent)
        out.append((bytes(mv[o:o + key_len[i]]),
                    default_ts if ts == _TS_ABSENT or ts == 0 else int(ts),
                    values[i]))
    return out


def decode_blocks(buf, off: np.ndarray, sz: np.ndarray, mt: np.ndarray,
                  first: np.ndarray, cnt: np.ndarray, out: np.ndarray,
                  validate_ts: bool) -> None:
    """Batched block decode: K payloads at buf[off[i]:off[i]+sz[i]] (zstd
    inline for MarshalType 5/6) -> int64s written contiguously into `out`
    (pre-sized to cnt.sum()). buf may be any buffer (bytes/mmap/ndarray).
    Raises ValueError naming the malformed block."""
    lib = _load()
    if isinstance(buf, np.ndarray):
        base = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    else:  # bytes: zero-copy via c_char_p
        base = ctypes.cast(ctypes.c_char_p(buf),
                           ctypes.POINTER(ctypes.c_uint8))
    k = int(off.size)
    r = lib.vm_decode_blocks(
        base, _as_i64_ptr(off), _as_i64_ptr(sz),
        mt.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        _as_i64_ptr(first), _as_i64_ptr(cnt), k, _as_i64_ptr(out),
        1 if validate_ts else 0)
    if r != int(cnt.sum()):
        raise ValueError(f"native decode_blocks: malformed block {-r - 1}")


def _as_base_ptr(buf):
    if isinstance(buf, np.ndarray):
        return buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    return ctypes.cast(ctypes.c_char_p(buf), ctypes.POINTER(ctypes.c_uint8))


def assemble_part(ts_buf, val_buf, ts_off, ts_sz, ts_mt, ts_first,
                  val_off, val_sz, val_mt, val_first, cnt, exps,
                  lo: int, hi: int):
    """Fused per-part read kernel (vm_assemble_part): decode K blocks'
    timestamp+value streams from the part's mmap'd payload buffers, clip
    each block to [lo, hi], convert kept mantissas to float64 with the
    block exponents, and compact into freshly allocated output columns —
    ONE GIL-released call per part. Returns (kept_per_block int64[K],
    ts int64[kept], vals float64[kept]); the ts/vals arrays are zero-copy
    views of the kernel-filled buffers. Raises on a malformed block."""
    lib = _load()
    k = int(cnt.size)
    total = int(cnt.sum())
    out_ts = np.empty(total, np.int64)
    out_vals = np.empty(total, np.float64)
    out_cnt = np.empty(k, np.int64)
    r = lib.vm_assemble_part(
        _as_base_ptr(ts_buf), _as_base_ptr(val_buf),
        _as_i64_ptr(ts_off), _as_i64_ptr(ts_sz),
        ts_mt.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        _as_i64_ptr(ts_first),
        _as_i64_ptr(val_off), _as_i64_ptr(val_sz),
        val_mt.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        _as_i64_ptr(val_first),
        _as_i64_ptr(cnt), _as_i64_ptr(exps), k, int(lo), int(hi),
        _as_i64_ptr(out_ts),
        out_vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i64_ptr(out_cnt))
    if r < 0:
        raise ValueError(f"native assemble_part: malformed block {-r - 1}")
    return out_cnt, out_ts[:r], out_vals[:r]


def dedup_rows(ts2: np.ndarray, v2: np.ndarray, counts: np.ndarray,
               rows: np.ndarray, interval_ms: int, pad_ts: int) -> None:
    """In-place per-row dedup + exact-duplicate removal over the padded
    (S, N) layout for the listed rows (vm_dedup_rows; bit-exact with
    storage/dedup.deduplicate + the keep-last pass). ts2/v2 may be
    column-sliced views (row stride is passed through); counts is
    rewritten in place."""
    lib = _load()
    if ts2.strides[1] != 8 or v2.strides[1] != 8:
        raise ValueError("dedup_rows needs row-contiguous columns")
    rows = np.ascontiguousarray(rows, np.int64)
    lib.vm_dedup_rows(
        _as_i64_ptr(ts2), ts2.strides[0] // 8,
        v2.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        v2.strides[0] // 8, _as_i64_ptr(counts), _as_i64_ptr(rows),
        int(rows.size), int(interval_ms), int(pad_ts))


def decimal_to_float_blocks(m: np.ndarray, group_offsets: np.ndarray,
                            exps: np.ndarray, out: np.ndarray) -> None:
    """Batched mantissa->float64: group i = m[group_offsets[i]:
    group_offsets[i+1]] with decimal exponent exps[i], written into out
    (same layout). Replicates ops/decimal.decimal_to_float bit-exactly."""
    lib = _load()
    k = int(group_offsets.size) - 1
    lib.vm_decimal_to_float_blocks(
        _as_i64_ptr(m), _as_i64_ptr(group_offsets), _as_i64_ptr(exps), k,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))


def f2d_grouped(values: np.ndarray, starts: np.ndarray):
    """Grouped float64 -> (int64 mantissas, per-group exponents), the
    native twin of ops/decimal.float_to_decimal_grouped (flush hot path).
    Returns None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    v = np.ascontiguousarray(values, dtype=np.float64)
    st = np.ascontiguousarray(starts, dtype=np.int64)
    m_out = np.empty(v.size, np.int64)
    exps = np.empty(st.size, np.int64)
    lib.vm_f2d_grouped(
        v.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i64_ptr(st), st.size, v.size, _as_i64_ptr(m_out),
        _as_i64_ptr(exps))
    return m_out, exps


def pack_delta_planes(ts: np.ndarray, m: np.ndarray, starts: np.ndarray,
                      start_ms: int, rebase: bool, width: int,
                      vals: np.ndarray | None = None, gate: float = 0.0):
    """The cold tile build's delta planes for S rows in one GIL-released
    pass (vm_pack_delta_planes; ops/device_decode._pack_py is its NumPy
    twin): row i is ts/m[starts[i]:starts[i+1]] (int64, contiguous).
    Returns None when the library is missing, else (planes, v0, risky):
    planes = (ts_first, ts_fdelta, ts_d2 [S, width], val_first,
    val_fdelta, val_d2, ts_max, val_max), all int32 but the int64 row
    maxima of |d2|, or None where a row is empty or needs more than int32;
    with `vals` (f32 tiles) v0 [S] and risky are the rebase gates at
    `gate`, else None and False."""
    lib = _load()
    if lib is None:
        return None
    ts = np.ascontiguousarray(ts, dtype=np.int64)
    m = np.ascontiguousarray(m, dtype=np.int64)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    if vals is not None:
        vals = np.ascontiguousarray(vals, dtype=np.float64)
    if m.size != ts.size or (vals is not None and vals.size != ts.size):
        raise ValueError("pack_delta_planes: ts, m and vals differ in size")
    S = int(starts.size)
    pi32 = ctypes.POINTER(ctypes.c_int32)
    vec = [np.empty(S, np.int32) for _ in range(4)]
    d2 = [np.empty((S, width), np.int32) for _ in range(2)]
    mx = [np.empty(S, np.int64) for _ in range(2)]
    v0 = np.empty(S, np.float64) if vals is not None else None
    risky = ctypes.c_int32(0)
    r = lib.vm_pack_delta_planes(
        _as_i64_ptr(ts), _as_i64_ptr(m),
        None if vals is None else
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i64_ptr(starts), S, int(ts.size), int(start_ms),
        1 if rebase else 0, int(width), float(gate),
        *(a.ctypes.data_as(pi32) for a in vec + d2),
        *(_as_i64_ptr(a) for a in mx),
        None if v0 is None else
        v0.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ctypes.byref(risky))
    planes = None if r else (vec[0], vec[1], d2[0], vec[2], vec[3], d2[1],
                             mx[0], mx[1])
    return planes, v0, bool(risky.value)


def clip_blocks(ts: np.ndarray, bstart: np.ndarray, bend: np.ndarray,
                lo: int, hi: int):
    """Per-block [lo, hi]-inclusive kept row range over the concatenated
    (per-block sorted) timestamp column: block i spans rows
    [bstart[i], bend[i]). Returns (keep_lo, keep_hi) index arrays."""
    lib = _load()
    k = int(bstart.size)
    out_lo = np.empty(k, np.int64)
    out_hi = np.empty(k, np.int64)
    lib.vm_clip_blocks(_as_i64_ptr(ts), _as_i64_ptr(bstart),
                       _as_i64_ptr(bend), k, int(lo), int(hi),
                       _as_i64_ptr(out_lo), _as_i64_ptr(out_hi))
    return out_lo, out_hi


def gather_rows2(a: np.ndarray, b: np.ndarray, keep_lo: np.ndarray,
                 keep_hi: np.ndarray, total: int):
    """Densely gather kept row ranges of two parallel int64 columns (per-
    segment memcpy; `total` = sum of range lengths)."""
    lib = _load()
    out_a = np.empty(total, np.int64)
    out_b = np.empty(total, np.int64)
    lib.vm_gather_rows2(_as_i64_ptr(a), _as_i64_ptr(b),
                        _as_i64_ptr(keep_lo), _as_i64_ptr(keep_hi),
                        int(keep_lo.size), _as_i64_ptr(out_a),
                        _as_i64_ptr(out_b))
    return out_a, out_b


def scatter_pad(ts_all: np.ndarray, vals_f: np.ndarray, cnts: np.ndarray,
                rows: np.ndarray, S: int, N: int, pad_ts: int):
    """Scatter pre-grouped blocks into padded (S, N) tiles; returns
    (ts2, v2, counts). Appends block k's samples to row rows[k] in input
    order, pads row tails with (pad_ts, 0.0)."""
    lib = _load()
    ts2 = np.empty((S, N), np.int64)
    v2 = np.empty((S, N), np.float64)
    fill = np.zeros(S, np.int64)
    lib.vm_scatter_pad(
        _as_i64_ptr(ts_all),
        vals_f.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i64_ptr(cnts), _as_i64_ptr(rows), int(cnts.size), int(S),
        int(N), int(pad_ts), _as_i64_ptr(ts2),
        v2.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i64_ptr(fill))
    return ts2, v2, fill


def counter_resets_2d(v: np.ndarray) -> np.ndarray:
    """Row-batched counter-reset removal; v is (S, N) or (N,) float64."""
    lib = _load()
    a = np.ascontiguousarray(v, dtype=np.float64)
    shape = a.shape
    if a.ndim == 1:
        a = a.reshape(1, -1)
    out = np.empty_like(a)
    lib.vm_counter_resets_2d(
        a.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        a.shape[0], a.shape[1],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return out.reshape(shape)


ROLLUP_COUNTER_FUNCS = {"rate": 1, "increase": 2, "increase_pure": 7,
                        "delta": 3, "deriv_fast": 4, "irate": 5, "idelta": 6}


def rollup_counter_2d(func: str, ts2: np.ndarray, v2: np.ndarray,
                      counts: np.ndarray, start: int, end: int, step: int,
                      lookback: int, mpi: np.ndarray) -> np.ndarray:
    """Fused native window-walk for the counter/derivative rollup family;
    returns (S, T) float64. Semantics match rollup_batch_packed bit-exactly
    (shared differential tests)."""
    lib = _load()
    S, N = ts2.shape
    T = (end - start) // step + 1
    ts2 = np.ascontiguousarray(ts2, dtype=np.int64)
    v2 = np.ascontiguousarray(v2, dtype=np.float64)
    counts = np.ascontiguousarray(counts, dtype=np.int64)
    mpi = np.ascontiguousarray(mpi, dtype=np.int64)
    out = np.empty((S, T), np.float64)
    scratch = np.empty(max(N, 1), np.float64)
    lib.vm_rollup_counter_2d(
        _as_i64_ptr(ts2), v2.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i64_ptr(counts), S, N, start, end, step, lookback,
        _as_i64_ptr(mpi), ROLLUP_COUNTER_FUNCS[func],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), scratch.ctypes.
        data_as(ctypes.POINTER(ctypes.c_double)))
    return out


def snappy_uncompress(data: bytes):
    """Native snappy block-format decompress; None when unavailable or
    malformed (callers fall back to the Python decoder)."""
    lib = _load()
    if lib is None:
        return None
    src = _as_u8_ptr(data)
    n = lib.vm_snappy_uncompressed_len(src, len(data))
    if n < 0 or n > 1 << 31:
        # unreasonable claimed length (attacker-controlled varint): refuse
        # to allocate; the Python decoder raises the proper 400 downstream
        return None
    out = ctypes.create_string_buffer(int(n) or 1)
    w = lib.vm_snappy_uncompress(src, len(data),
                                 ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)),
                                 n)
    if w != n:
        return None
    return out.raw[:n]


class ColumnarRows:
    """Columnar ingest rows: keybuf[key_off[i]:key_off[i]+key_len[i]] is the
    canonical text series key of row i; tss/values are int64/float64."""

    __slots__ = ("keybuf", "key_off", "key_len", "tss", "values")

    def __init__(self, keybuf, key_off, key_len, tss, values):
        self.keybuf = keybuf
        self.key_off = key_off
        self.key_len = key_len
        self.tss = tss
        self.values = values

    def __len__(self):
        return self.key_off.size

    def to_rows(self):
        """Materialize per-row (key_bytes, ts, value) tuples (slow; tests
        and non-columnar storages only)."""
        mv = memoryview(self.keybuf)
        return [(bytes(mv[o:o + l]), int(t), float(v))
                for o, l, t, v in zip(self.key_off, self.key_len,
                                      self.tss, self.values)]


def _parse_columnar(call, data: bytes, est_rows: int):
    """Shared retry driver for the columnar parsers: grows keybuf (-2) and
    row capacity (-3); -1 = native asked for the Python fallback."""
    lib = _load()
    if lib is None:
        return None
    keybuf_cap = 2 * len(data) + 4096
    max_rows = est_rows
    for _ in range(6):
        keybuf = ctypes.create_string_buffer(keybuf_cap)
        key_off = np.empty(max_rows, dtype=np.int64)
        key_len = np.empty(max_rows, dtype=np.int64)
        values = np.empty(max_rows, dtype=np.float64)
        tss = np.empty(max_rows, dtype=np.int64)
        n = call(lib, keybuf, keybuf_cap, _as_i64_ptr(key_off),
                 _as_i64_ptr(key_len),
                 values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                 _as_i64_ptr(tss), max_rows)
        if n == -2:
            keybuf_cap *= 4
            continue
        if n == -3:
            max_rows *= 4
            continue
        if n < 0:
            return None
        return ColumnarRows(keybuf.raw[:_keybuf_used(key_off, key_len, n)],
                            key_off[:n], key_len[:n], tss[:n], values[:n])
    return None


def _keybuf_used(key_off, key_len, n):
    if n == 0:
        return 0
    return int(key_off[n - 1] + key_len[n - 1])


def parse_rw_columnar(data: bytes, default_ts: int):
    """Native remote-write WriteRequest parse (uncompressed protobuf) ->
    ColumnarRows; None = fall back to the Python parser."""
    return _parse_columnar(
        lambda lib, kb, kc, ko, kl, vs, ts, mr: lib.vm_parse_rw(
            _as_u8_ptr(data), len(data), default_ts, ctypes.cast(
                kb, ctypes.POINTER(ctypes.c_uint8)), kc, ko, kl, vs, ts, mr),
        data, max(data.count(b"\x12") + 16, 64))


def parse_influx_columnar(data: bytes, db: str, default_ts: int):
    """Native influx line-protocol parse -> ColumnarRows; None = fallback."""
    dbb = db.encode() if db else b""
    return _parse_columnar(
        lambda lib, kb, kc, ko, kl, vs, ts, mr: lib.vm_parse_influx(
            _as_u8_ptr(data), len(data), _as_u8_ptr(dbb), len(dbb),
            default_ts, ctypes.cast(kb, ctypes.POINTER(ctypes.c_uint8)),
            kc, ko, kl, vs, ts, mr),
        data, max(2 * data.count(b"\n") + 16, 64))


def parse_prom_columnar(data: bytes, default_ts: int):
    """Native prometheus text parse -> ColumnarRows (keys reference the
    request body itself); None = fallback."""
    lib = _load()
    if lib is None:
        return None
    n_max = data.count(b"\n") + 2
    key_off = np.empty(n_max, dtype=np.int32)
    key_len = np.empty(n_max, dtype=np.int32)
    values = np.empty(n_max, dtype=np.float64)
    tss = np.empty(n_max, dtype=np.int64)
    n = lib.vm_parse_prom(
        data, len(data),
        key_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        key_len.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i64_ptr(tss), n_max)
    tss = tss[:n]
    # explicit 0 is "no timestamp" too (parity with parse_prom_raw)
    tss[(tss == _TS_ABSENT) | (tss == 0)] = default_ts
    return ColumnarRows(data, key_off[:n].astype(np.int64),
                        key_len[:n].astype(np.int64), tss, values[:n])


class KeyMap:
    """Native byte-string -> dense-id map (vm_keymap). Ids are assigned
    consecutively in first-occurrence order, so id arrays can index numpy
    side tables (TSID fields, per-day state) directly."""

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.vm_keymap_new()
        if not self._h:
            raise MemoryError("vm_keymap_new failed")

    def __len__(self):
        return int(self._lib.vm_keymap_size(self._h))

    def resolve(self, base, key_off: np.ndarray,
                key_len: np.ndarray) -> tuple[np.ndarray, int]:
        """Returns (ids int64[n], n_new). New keys get ids
        len-before..len-before+n_new-1 in first-occurrence order."""
        n = int(key_off.size)
        ids = np.empty(n, dtype=np.int64)
        if isinstance(base, np.ndarray):
            bp = base.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        else:
            bp = _as_u8_ptr(base)
        added = self._lib.vm_keymap_resolve(
            self._h, bp, _as_i64_ptr(np.ascontiguousarray(key_off, np.int64)),
            _as_i64_ptr(np.ascontiguousarray(key_len, np.int64)), n,
            _as_i64_ptr(ids))
        if added < 0:
            raise MemoryError("vm_keymap_resolve failed")
        return ids, int(added)

    def close(self):
        if self._h:
            self._lib.vm_keymap_free(self._h)
            self._h = 0

    def __del__(self):
        try:
            self.close()
        except (AttributeError, TypeError, OSError):
            # interpreter teardown: the ctypes lib handle may already
            # be gone; __del__ must never raise
            pass


def marshal_i64_many(vals: np.ndarray, offsets: np.ndarray):
    """Batched block marshal: type choice + encode for K blocks in one
    native call. vals = int64 concatenation, offsets = K+1 boundaries.
    Returns (payload bytes, types int32[K], firsts int64[K], lens int64[K])
    or None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    k = offsets.size - 1
    cap = int(vals.size + k) * 10 + 16
    out = ctypes.create_string_buffer(cap)
    types = np.empty(k, dtype=np.int32)
    firsts = np.empty(k, dtype=np.int64)
    lens = np.empty(k, dtype=np.int64)
    n = lib.vm_marshal_i64_many(
        _as_i64_ptr(vals), _as_i64_ptr(offsets), k,
        ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)), cap,
        types.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        _as_i64_ptr(firsts), _as_i64_ptr(lens))
    if n < 0:
        raise ValueError("native batched marshal failed")
    return out.raw[:n], types, firsts, lens


#: the most bytes one point of a matrix answer takes in ``write_matrix``'s
#: text: ``[`` t ``, "`` v ``"]`` and the ``, `` before the next, with t
#: and v at 24 each (the longest ``repr(float)``); a row adds 2 brackets
MATRIX_POINT_MAX = 56


class _Spares:
    """The buffers of the last calls of one kind, for the next to write
    into: memory the process has not touched before costs 9 us a page on
    the chip's host (PERF.md section 6, PR 33), 5 ms for a 2.3 MB answer's
    text and 20 for a 9 MB one, every query that malloc hands out fresh
    pages.  ``take`` gives a buffer to one caller alone; one that an
    earlier answer's memoryviews still hold is left to them."""

    def __init__(self, dtype):
        self._dtype = dtype
        self._free: collections.deque = collections.deque(maxlen=2)

    def take(self, n: int) -> np.ndarray:
        try:
            buf = self._free.pop()
        except IndexError:
            return np.empty(n, dtype=self._dtype)
        # 2: `buf` and getrefcount's own argument, so no view is left on it
        if buf.size >= n and sys.getrefcount(buf) == 2:
            return buf
        return np.empty(n, dtype=self._dtype)

    def give(self, buf: np.ndarray) -> None:
        self._free.append(buf)


_matrix_blocks = _Spares(np.float64)
_matrix_texts = _Spares(np.uint8)


def write_matrix(grid_s: np.ndarray, rows):
    """The ``values`` text of a ``query_range`` answer in one native
    call: grid_s = float64 [T] seconds, rows = R float64 [T] arrays (or
    one [R, T] block; NaN = absent).  Returns (buf, row_starts int64 [R],
    row_ends int64 [R], n_points, n_ranges): row i's text ``[[t, "v"],
    ...]`` is ``buf[row_starts[i]:row_ends[i]]``, empty for a row with no
    point; byte for byte what ``json.dumps`` makes of ``[[float(t),
    fmt_value(v)], ...]``.  The call cuts the rows into n_ranges ranges,
    written at once by as many threads, by the points it sees and the
    machine's cores (1: a small answer, written on the calling thread);
    the text between two ranges' rows is not the answer's.  None when the
    native library is unavailable."""
    return _write_matrix(grid_s, rows, None)


def write_matrix_cut(grid_s: np.ndarray, rows, ranges: int):
    """``write_matrix`` with the number of ranges given (held to 1..R)
    instead of observed: what the tests, and the measurement the width
    was set from, hold the served call to."""
    return _write_matrix(grid_s, rows, ranges)


def _write_matrix(grid_s, rows, ranges):
    lib = _load()
    if lib is None:
        return None
    grid_s = np.ascontiguousarray(grid_s, dtype=np.float64)
    r, t = len(rows), grid_s.size
    flat = _matrix_blocks.take(r * t)
    block = flat[:r * t].reshape(r, t)
    if r:
        np.stack(rows, out=block)  # ValueError unless every row is [T]
    cap = r * (t * MATRIX_POINT_MAX + 2)
    out = _matrix_texts.take(cap)
    row_starts = np.empty(r, dtype=np.int64)
    row_ends = np.empty(r, dtype=np.int64)
    n_points = ctypes.c_int64()
    pf64 = ctypes.POINTER(ctypes.c_double)
    args = (grid_s.ctypes.data_as(pf64), t, block.ctypes.data_as(pf64), r,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
            _as_i64_ptr(row_starts), _as_i64_ptr(row_ends),
            ctypes.byref(n_points))
    if ranges is None:
        n_ranges = ctypes.c_int64()
        n = lib.vm_write_matrix(*args, ctypes.byref(n_ranges))
        ranges = n_ranges.value
    else:
        ranges = max(1, min(int(ranges), r))
        n = lib.vm_write_matrix_cut(*args, ranges)
    _matrix_blocks.give(flat)
    if not 0 <= n <= cap:
        raise ValueError(f"native matrix writer: {n} of {cap} bytes")
    buf = memoryview(out)[:n]
    _matrix_texts.give(out)
    return buf, row_starts, row_ends, n_points.value, ranges


def pending_order(chunks: list, rank: np.ndarray, n_ranks: int,
                  mid: np.ndarray, max_block_rows: int):
    """The rows of a batch of pending chunks (`(ids, ts, vals)` arrays of
    each, ingest order) in (TSID, ts) order, stable: `rank` is the id
    space's int32 rank of every dense id in TSID order (equal keys share
    one, `n_ranks` of them), `mid` its uint64 metric-id column.  Returns
    (ts, vals, ids, mids) of the ordered rows and the start row of every
    block (a run of one metric id, cut every `max_block_rows`); None when
    the library is missing or an id lies outside the rank."""
    lib = _load()
    if lib is None:
        return None
    k = len(chunks)
    cols = [(np.ascontiguousarray(i, np.int64),
             np.ascontiguousarray(t, np.int64),
             np.ascontiguousarray(v, np.float64)) for i, t, v in chunks]
    if any(not i.size == t.size == v.size for i, t, v in cols):
        raise ValueError("a pending chunk's columns differ in length")
    ptrs = [(ctypes.c_void_p * k)(*[c[j].ctypes.data for c in cols])
            for j in range(3)]
    lens = np.fromiter((c[0].size for c in cols), np.int64, k)
    n = int(lens.sum())
    rank = np.ascontiguousarray(rank, np.int32)
    mid = np.ascontiguousarray(mid, np.uint64)
    ts = np.empty(n, np.int64)
    vals = np.empty(n, np.float64)
    ids = np.empty(n, np.int64)
    mids = np.empty(n, np.uint64)
    starts = np.empty(n, np.int64)
    nb = lib.vm_pending_order(
        ptrs[0], ptrs[1], ptrs[2], _as_i64_ptr(lens), k,
        rank.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        min(rank.size, mid.size), int(n_ranks), mid.ctypes.data,
        int(max_block_rows), _as_i64_ptr(ts),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _as_i64_ptr(ids), mids.ctypes.data, _as_i64_ptr(starts))
    if nb < 0:
        return None
    return ts, vals, ids, mids, starts[:nb].copy()
