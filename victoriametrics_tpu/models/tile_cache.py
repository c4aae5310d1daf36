"""HBM-resident tile cache for the query engine.

The reference keeps decompressed index blocks in a RAM blockcache sized at
10% of memory (lib/blockcache, lib/storage/part.go:15-22) and relies on the
page cache for data blocks; repeated queries run hot. The TPU analog: packed
(series, sample) tiles live in HBM between queries, keyed by (part id, tile
id, revision). Evictions are LRU by bytes.

Uploads are chunked: device_put goes up in <=8MB row slices re-assembled
on device (what that buys on a v5e host's link: not measured).
"""

from __future__ import annotations

import collections
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..devtools.locktrace import make_lock
from ..devtools.racetrace import traced_fields
from ..utils import costacc as _costacc
from ..utils import flightrec as _flightrec
from ..utils import metrics as metricslib

UPLOAD_CHUNK_BYTES = 8 << 20

# device-plane link accounting: EVERY host->device and device->host byte
# of the query engine funnels through count_upload/count_download (the
# residency guard test asserts a rolling refresh uploads only tail
# columns, and the benchmark divides the uploaded bytes by queries)
_BYTES_UPLOADED = metricslib.REGISTRY.counter(
    "vm_device_bytes_uploaded_total")
_BYTES_DOWNLOADED = metricslib.REGISTRY.counter(
    "vm_device_bytes_downloaded_total")


def count_upload(nbytes: int) -> None:
    _BYTES_UPLOADED.inc(int(nbytes))
    _costacc.add_device(up=int(nbytes))


def count_download(nbytes: int) -> None:
    _BYTES_DOWNLOADED.inc(int(nbytes))
    _costacc.add_device(down=int(nbytes))


def bytes_uploaded() -> int:
    return _BYTES_UPLOADED.get()


def timed_transfer(span: str, nbytes: int, fn):
    """Run one H2D/D2H transfer `fn`, counting its bytes and timing it
    as a `span` phase whatever its size (a dashboard tick's sub-MiB
    append is a transfer too) — the ONE place the device:upload /
    device:download phase is defined (shard_put, chunked_device_put and
    the kernel-result pull all funnel here)."""
    (count_upload if span == "device:upload" else count_download)(nbytes)
    with _flightrec.phase(span, arg=nbytes):
        return fn()


# cache self-metrics (reference vm_cache_{requests,misses}_total +
# vm_cache_{size_bytes,entries}{type=...}); gauges sum over every live
# TileCache so embedded/test setups with several engines stay correct
_instances: "weakref.WeakSet[TileCache]" = weakref.WeakSet()
_CACHE_REQUESTS = metricslib.REGISTRY.counter(
    'vm_cache_requests_total{type="tpu/tile_cache"}')
_CACHE_MISSES = metricslib.REGISTRY.counter(
    'vm_cache_misses_total{type="tpu/tile_cache"}')
metricslib.REGISTRY.gauge(
    'vm_cache_size_bytes{type="tpu/tile_cache"}',
    callback=lambda: sum(c.size_bytes for c in list(_instances)))
metricslib.REGISTRY.gauge(
    'vm_cache_entries{type="tpu/tile_cache"}',
    callback=lambda: sum(c.entry_count() for c in list(_instances)))


def chunked_device_put(x: np.ndarray, device=None) -> jax.Array:
    """device_put in <=8MB row-slices, concatenated on device."""
    device = device or jax.devices()[0]
    return timed_transfer("device:upload", x.nbytes,
                          lambda: _chunked_device_put(x, device))


def _chunked_device_put(x: np.ndarray, device) -> jax.Array:
    nbytes = x.nbytes
    if nbytes <= UPLOAD_CHUNK_BYTES or x.ndim == 0 or x.shape[0] <= 1:
        return jax.device_put(x, device)
    rows_per_chunk = max(1, UPLOAD_CHUNK_BYTES // max(x.nbytes // x.shape[0], 1))
    parts = [jax.device_put(x[i:i + rows_per_chunk], device)
             for i in range(0, x.shape[0], rows_per_chunk)]
    return jnp.concatenate(parts, axis=0)


# device-resident window cache health: hits = refreshes served from an
# HBM-resident window (rolling advance or warm exact-key reuse) without
# re-uploading the window; evictions = resident windows dropped by the
# LRU bound; compactions = on-device window slides (samples older than
# the fetch bound dropped + tile origin rebased, instead of a full
# re-upload when headroom/int32 run out)
_WINDOW_HITS = metricslib.REGISTRY.counter(
    "vm_device_window_cache_hits_total")
_WINDOW_EVICTIONS = metricslib.REGISTRY.counter(
    "vm_device_window_cache_evictions_total")
_WINDOW_COMPACTIONS = metricslib.REGISTRY.counter(
    "vm_device_window_compactions_total")


def device_resident_enabled() -> bool:
    """Device data residency on?  VM_DEVICE_RESIDENT=0 disables every
    resident-window reuse path (rolling advance, warm exact-key tile
    reuse) so each query re-uploads its full window — the loud full-upload
    escape hatch AND the equality oracle the residency tests diff
    against."""
    return os.environ.get("VM_DEVICE_RESIDENT", "1") != "0"


def count_window_hit() -> None:
    _WINDOW_HITS.inc()


def count_window_compaction() -> None:
    _WINDOW_COMPACTIONS.inc()


class DeviceWindowCache:
    """Host-side registry of device-RESIDENT rolling windows (the
    DeviceWindowCache of ISSUE 12): each entry pins the device buffers of
    one query shape's packed (S, T) window (RollingTile) plus its group
    assignment and the host-side ring copy of the [G, T] aggregate, so a
    rolling refresh uploads only the suffix tail columns and the rollup
    never re-crosses the host boundary until the final [G, T] pull.

    Entry-count LRU (VM_DEVICE_WINDOWS, default 256): each window's HBM
    cost is bounded by the tile shapes, and the entries that matter (live
    dashboards) are re-touched every refresh.  Evictions tick
    vm_device_window_cache_evictions_total — a steadily climbing eviction
    counter on a stable dashboard fleet means the cap is too small."""

    def __init__(self, cap: int | None = None):
        if cap is None:
            try:
                cap = int(os.environ.get("VM_DEVICE_WINDOWS", "256"))
            except ValueError:
                cap = 256
        self.cap = max(cap, 1)
        self._lock = make_lock("models.DeviceWindowCache._lock")
        self._entries: collections.OrderedDict = collections.OrderedDict()

    def get(self, key):
        with self._lock:
            v = self._entries.get(key)
            if v is not None:
                self._entries.move_to_end(key)
            return v

    def peek(self, key):
        """get() without the LRU touch (readiness probes must not keep an
        otherwise-dead entry alive)."""
        with self._lock:
            return self._entries.get(key)

    def put(self, key, value) -> None:
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.cap:
                self._entries.popitem(last=False)
                _WINDOW_EVICTIONS.inc()

    def invalidate(self, key=None) -> None:
        with self._lock:
            if key is None:
                self._entries.clear()
            else:
                self._entries.pop(key, None)

    def entry_count(self) -> int:
        with self._lock:
            return len(self._entries)


@traced_fields("_entries", "_sizes", "_bytes")
class TileCache:
    """LRU byte-bounded cache of device-resident pytrees."""

    def __init__(self, capacity_bytes: int, device=None):
        self.capacity = capacity_bytes
        self.device = device or jax.devices()[0]
        # through the locktrace seam: the racetrace sanitizer needs the
        # release->acquire clock edge to see these accesses as ordered
        self._lock = make_lock("models.TileCache._lock")
        self._entries: collections.OrderedDict[object, tuple] = \
            collections.OrderedDict()
        self._sizes: dict[object, int] = {}
        self._bytes = 0
        # per-instance thread-safe counters (the global vm_cache_* metrics
        # above aggregate over instances; these feed per-cache stats)
        self._hits = metricslib.Counter("hits")
        self._misses = metricslib.Counter("misses")
        _instances.add(self)

    @property
    def hits(self) -> int:
        return self._hits.get()

    @property
    def misses(self) -> int:
        return self._misses.get()

    def _tree_bytes(self, tree) -> int:
        total = 0
        for a in jax.tree_util.tree_leaves(tree):
            if hasattr(a, "shape") and hasattr(a, "dtype"):
                total += int(np.prod(a.shape)) * a.dtype.itemsize
            elif hasattr(a, "offsets"):  # V0Info host companion
                total += a.offsets.nbytes
        return total

    def get(self, key):
        _CACHE_REQUESTS.inc()
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self._hits.inc()
                return self._entries[key]
        self._misses.inc()
        _CACHE_MISSES.inc()
        return None

    def put(self, key, host_tree):
        """Upload a pytree of numpy arrays; returns the device tree. A tree
        larger than the whole cache budget is uploaded and returned but NOT
        retained (it would evict everything and still overcommit HBM)."""
        dev_tree = jax.tree_util.tree_map(
            lambda a: chunked_device_put(np.asarray(a), self.device), host_tree)
        size = self._tree_bytes(dev_tree)
        if size > self.capacity:
            # too big to retain — but a stale entry under this key must not
            # keep serving old data
            self.invalidate(key)
            return dev_tree
        with self._lock:
            if key in self._entries:
                self._bytes -= self._sizes.pop(key)
                del self._entries[key]
            while self._bytes + size > self.capacity and self._entries:
                old, _ = self._entries.popitem(last=False)
                self._bytes -= self._sizes.pop(old)
            self._entries[key] = dev_tree
            self._sizes[key] = size
            self._bytes += size
        return dev_tree

    def put_device(self, key, dev_tree):
        """Retain an already-device-resident pytree (e.g. tiles decoded on
        device from compact planes)."""
        size = self._tree_bytes(dev_tree)
        if size > self.capacity:
            self.invalidate(key)
            return dev_tree
        with self._lock:
            if key in self._entries:
                self._bytes -= self._sizes.pop(key)
                del self._entries[key]
            while self._bytes + size > self.capacity and self._entries:
                old, _ = self._entries.popitem(last=False)
                self._bytes -= self._sizes.pop(old)
            self._entries[key] = dev_tree
            self._sizes[key] = size
            self._bytes += size
        return dev_tree

    def invalidate(self, key=None):
        with self._lock:
            if key is None:
                self._entries.clear()
                self._sizes.clear()
                self._bytes = 0
            elif key in self._entries:
                self._bytes -= self._sizes.pop(key)
                del self._entries[key]

    def entry_count(self) -> int:
        # locked: a /metrics scrape must not read len() mid-evict
        with self._lock:
            return len(self._entries)

    @property
    def size_bytes(self) -> int:
        # locked: a /metrics scrape must not read mid-evict
        with self._lock:
            return self._bytes
