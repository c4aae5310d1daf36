"""Flight recorder: cross-thread latency attribution for the serving
hot path (the per-event sibling of the aggregate phase counters, and
the cross-thread extension of the reference's query tracer + slow-query
log: lib/querytracer sees one query's own spans, this sees what ELSE the
process was doing while the query ran).

Always-on, low-overhead: every thread that records owns a private
bounded ring of (t0, dur, name, ctx, arg, depth) event slots.  The ring
starts small and doubles up to its capacity as the thread records (a
per-connection thread's few dozen events never pay for 8192 slots);
between doublings the record path is index arithmetic + six slot
stores + one integer bump — no allocation, no lock, no syscall.
Writers never synchronize with readers: a capture snapshots each ring's
write cursor and walks backward, and any slot the writer overtook
mid-read is discarded by re-checking the cursor (the classic
seqlock-reader discipline, per-slot granularity is one event so a torn
event can only be dropped, never misattributed).

Event model: COMPLETE spans (Chrome trace ``"ph": "X"``) recorded at
END time — callers time the region themselves (they already do, for the
phase counters) and call :func:`rec` once.  Instant events
(``"ph": "i"``) mark decisions (cache inplace/rebuild, merge-gate
yields).  Timestamps are ``time.perf_counter()`` floats — one monotonic
clock shared by every thread, so cross-thread overlap is meaningful.

The phase seam: :func:`phase` is the ONE way a timed region of the
served query is recorded.  ``with flightrec.phase("cache:put"):`` keeps
a per-thread stack, so every phase is charged its SELF time (entering a
child stops the parent's clock, leaving it restarts it) and the self
times of a request partition the wall between its root's entry and
exit exactly.  On exit one call feeds the ring (inclusive ``dur`` plus
the nesting ``depth``), the current query's cost tracker (self wall +
self thread-CPU, bucket = name), ``vm_query_phase_seconds_total{phase=}``
(only on a thread that holds a request root) and — through
:func:`set_annotator`, installed by the device engine, because this
module must import without jax — a ``vm:<name>`` annotation on the
profiler's host plane.  Regions with no natural block (background
flush/merge workers, the pool's task wrapper, instants) still call
:func:`rec` directly.

Cross-thread attribution: a serving thread opens a *flight context*
(:func:`set_ctx`, an integer id per refresh/query); utils/workpool
propagates the submitting thread's ctx to its pool workers around each
task, so fetch/decode spans executed on workers carry the query's ctx
and :func:`ctx_events` can reassemble one query's work from every
thread's ring (the per-phase split the slow-query log records).

Capture: :meth:`FlightRecorder.capture` merges the live window of all
thread rings into one Chrome trace-event-format JSON object
(Perfetto/chrome://tracing-loadable) and keeps it in a bounded ring of
recent captures served at ``/api/v1/status/flight``.  The serving layer
triggers a capture when a refresh exceeds ``VM_SLOW_REFRESH_MS``;
anything can trigger one on demand.

``VM_FLIGHTREC=0`` is the escape hatch: :func:`rec`/:func:`instant`
return after one global-flag check and captures return empty.

Self-metrics: ``vm_flight_captures_total``,
``vm_flight_dropped_events_total`` (ring-overwritten events noticed at
capture time), ``vm_flight_events_total`` is deliberately absent — a
per-event counter bump would double the record cost.
"""

from __future__ import annotations

import os
import threading
import time

from . import costacc
from . import metrics as metricslib

__all__ = ["enabled", "rec", "instant", "phase", "set_annotator",
           "new_ctx", "set_ctx", "get_ctx", "ctx_events", "clear_ctx",
           "RECORDER", "FlightRecorder", "reconfigure"]

#: ring capacity per thread (events); power of two for mask arithmetic
_DEFAULT_CAP = 1 << 13
#: slots a new ring starts with; it doubles up to its capacity as events
#: arrive.  A thread that serves one HTTP connection records a few dozen
#: events and its ring outlives it by the capture window: at full
#: capacity, a few hundred such rings are millions of list slots that
#: every full gc collection walks (read as ~8 % of a dashboard refresh's
#: latency on the chip host, PERF.md PR 28)
_INITIAL_SLOTS = 1 << 8


def _env_enabled() -> bool:
    return os.environ.get("VM_FLIGHTREC", "1") != "0"


def _env_cap() -> int:
    try:
        n = int(os.environ.get("VM_FLIGHTREC_EVENTS", "0"))
    except ValueError:
        n = 0
    if n <= 0:
        return _DEFAULT_CAP
    # round up to a power of two (the record path uses `& mask`)
    return 1 << max(n - 1, 1).bit_length()


_ENABLED = _env_enabled()


def enabled() -> bool:
    """True when the recorder is on (``VM_FLIGHTREC`` != 0)."""
    return _ENABLED


def reconfigure() -> None:
    """Re-read ``VM_FLIGHTREC`` (tests flip the env var mid-process;
    production reads it once at import)."""
    global _ENABLED
    _ENABLED = _env_enabled()


class _Ring:
    """One thread's event ring.  Only the owner thread writes; capture
    threads read racily and validate against the cursor afterward.

    Slots are parallel lists (not tuples), grown by doubling up to the
    capacity: between doublings a record is six slot stores + one cursor
    bump, allocating nothing."""

    __slots__ = ("t0", "dur", "name", "ctx", "arg", "depth", "i", "w",
                 "cap", "size", "mask", "tid", "tname", "taken", "thread")

    def __init__(self, cap: int, thread: threading.Thread):
        n = min(cap, _INITIAL_SLOTS)
        self.t0 = [0.0] * n
        self.dur = [0.0] * n
        self.name = [""] * n
        self.ctx = [0] * n
        self.arg = [None] * n
        self.depth = [0] * n  # phase nesting depth (0 = a plain rec)
        self.i = 0          # monotonic write cursor (slot = i & mask)
        self.w = -1         # cursor mid-store marker: w == i <=> in rec()
        self.cap = cap      # the most events the ring will ever hold
        self.size = n       # slots allocated so far (doubles up to cap)
        self.mask = n - 1
        self.tid = thread.ident or 0
        self.tname = thread.name
        self.taken = 0      # first cursor NOT yet included in a capture
        self.thread = thread    # liveness probe for ring reclamation

    def grow(self) -> None:
        """Double the slot lists (owner thread only, with the cursor AT
        `size`: nothing has wrapped yet, so event k sits in slot k under
        the old mask and the new one alike, and a racing reader that
        holds either sees the same events)."""
        n = self.size
        self.t0.extend([0.0] * n)
        self.dur.extend([0.0] * n)
        self.name.extend([""] * n)
        self.ctx.extend([0] * n)
        self.arg.extend([None] * n)
        self.depth.extend([0] * n)
        self.size = 2 * n
        self.mask = 2 * n - 1

    def newest_t0(self) -> float:
        """t0 of the most recent event (0.0 when empty); racy read, only
        meaningful for DEAD owners (no concurrent writer)."""
        if self.i == 0:
            return 0.0
        return self.t0[(self.i - 1) & self.mask]

    def snapshot(self, min_t0: float) -> list[tuple]:
        """Racy read of the live window: events with t0 >= min_t0, oldest
        first.  Slots overwritten while reading are re-checked against the
        advanced cursor and dropped (seqlock-reader discipline)."""
        end = self.i
        lo = max(end - self.cap, 0)
        out = []
        t0s, durs, names, ctxs, args, depths = (
            self.t0, self.dur, self.name, self.ctx, self.arg, self.depth)
        mask = self.mask
        for k in range(lo, end):
            j = k & mask
            t0 = t0s[j]
            if t0 < min_t0:
                continue
            out.append((t0, durs[j], names[j], ctxs[j], args[j], k,
                        depths[j]))
        # validate: any slot the writer lapped during the walk holds a
        # NEWER event than its cursor position promised — discard those.
        # STRICT bound: the writer stores the six slots BEFORE bumping
        # the cursor, so the slot at cursor (i - cap) may be mid-store
        # (torn) while i still reads one low — drop it too.  Costs at
        # most the single oldest event of an idle full ring; keeps the
        # "can drop, never misattribute" guarantee.
        min_keep = self.i - self.cap
        if min_keep >= lo:
            out = [e for e in out if e[5] > min_keep]
        return out


_tls = threading.local()

# every ring ever created (threads die, their last events remain
# capturable); appended under _rings_lock, iterated lock-free by capture
_rings: list[_Ring] = []
_rings_lock = threading.Lock()

_ctx_counter = [0]
_ctx_lock = threading.Lock()


def _prune_dead_rings(min_t0: float) -> None:
    """Drop rings whose owner thread died AND whose newest event has
    aged out of the capture window.  Without this, one ring per
    recording thread (e.g. per-connection HTTP handler threads) leaks
    forever; with it, a dead thread's last events stay capturable for
    the window and the ring list stays bounded by live threads +
    recently-dead ones.  Caller holds _rings_lock."""
    keep = [r for r in _rings
            if r.thread.is_alive() or r.newest_t0() >= min_t0]
    if len(keep) != len(_rings):
        _rings[:] = keep


def _prune_window_s() -> float:
    try:
        return float(os.environ.get("VM_FLIGHT_WINDOW_S", "60"))
    except ValueError:
        return 60.0


def _new_ring() -> _Ring:
    ring = _Ring(_env_cap(), threading.current_thread())
    with _rings_lock:
        _prune_dead_rings(time.perf_counter() - _prune_window_s())
        _rings.append(ring)
    return ring


def rec(name: str, t0: float, dur: float, arg=None, depth: int = 0) -> None:
    """Record one complete span [t0, t0+dur) (perf_counter seconds) on
    the calling thread's ring.  The hot-path primitive: one flag check,
    one TLS lookup, six slot stores, one cursor bump.  `depth` is the
    phase seam's nesting depth (1 = outermost phase on its thread); a
    direct call leaves it 0, "not a phase"."""
    if not _ENABLED:
        return
    ring = getattr(_tls, "ring", None)
    if ring is None:
        ring = _tls.ring = _new_ring()
    i = ring.i
    # w == i marks this slot mid-store: the gc hook (which can fire
    # DURING these stores — the cursor bump's int allocation, or the
    # ring's growth, can trigger a collection) checks it and stands down
    # instead of interleaving a second event into the same slot
    ring.w = i
    if i == ring.size and i < ring.cap:
        ring.grow()
    j = i & ring.mask
    ring.t0[j] = t0
    ring.dur[j] = dur
    ring.name[j] = name
    ring.ctx[j] = getattr(_tls, "ctx", 0)
    ring.arg[j] = arg
    ring.depth[j] = depth
    ring.i = i + 1


def instant(name: str, arg=None) -> None:
    """Record a zero-duration marker (a decision, not a region)."""
    if not _ENABLED:
        return
    rec(name, time.perf_counter(), 0.0, arg)


# -- the phase seam -----------------------------------------------------------

#: the served query's phases, all registered at import so every process
#: exports every member (0 where nothing ran) and a reader never has to
#: tell "absent" from "idle".  Self times of one request's phases
#: partition its root's wall, so the family sums to
#: ``vm_query_wall_seconds_total``.  Other names create their member on
#: first use.
QUERY_PHASES = (
    "serve:admission", "serve:rows", "serve:json", "serve:send",
    "serve:other", "eval:other", "eval:transform", "fetch:wait",
    "cache:get", "cache:put", "cache:merge",
    "device:tile_build", "device:upload", "device:execute",
    "device:download", "device:compile")


def _query_phase_counter(name: str):
    return metricslib.REGISTRY.float_counter(
        f'vm_query_phase_seconds_total{{phase="{name}"}}')


_QUERY_PHASE = {n: _query_phase_counter(n) for n in QUERY_PHASES}
_QUERY_WALL = metricslib.REGISTRY.float_counter(
    "vm_query_wall_seconds_total")

# name -> context-manager factory for the profiler's host plane
# (jax.profiler.TraceAnnotation once the device engine has started);
# None in processes that never start one
_annotator = None


def set_annotator(factory) -> None:
    """Install the host-plane annotation hook: ``factory(label)`` must
    return a context manager.  The device engine passes
    ``jax.profiler.TraceAnnotation`` when it starts, so a profiler
    session shows every phase as ``vm:<name>`` on the same clock as the
    device's ops; off a session the annotation is a sub-microsecond
    no-op.  ``None`` removes the hook."""
    global _annotator
    _annotator = factory


class phase:
    """``with flightrec.phase("serve:rows"):`` — one timed region of
    the served query.  Phases nest per thread and each is charged its
    SELF time: wall and thread-CPU between entry and exit, less what its
    child phases took.  On exit (also when the body raises) the region
    is recorded once, everywhere: a ring event (inclusive duration +
    nesting depth), a lap of the self time in the current query's cost
    tracker under ``name``, the phase's counter, and the profiler
    annotation held open since entry.

    `counter` names a family of the phase's OWN
    (``vm_fetch_phase_seconds_total``, ``vm_ingest_phase_seconds_total``):
    it gets the duration less what nested phases with a counter took
    (a stage carved out of another, so a family still sums to the time
    its stages covered), and the phase never charges
    ``vm_query_phase_seconds_total`` — its time stays with the enclosing
    query phase there (an inline fetch reads as ``fetch:wait`` exactly
    as a pooled one does).  Without `counter` the self time goes to
    ``vm_query_phase_seconds_total{phase=name}``, but only on a thread
    that holds a request root: that family is a partition of one
    thread's wall and must never sum past it.

    ``root=True`` opens a request: the outermost such phase on a thread
    gives the request its flight context (unless one is installed) and
    adds its whole wall to ``vm_query_wall_seconds_total`` on exit.

    :meth:`lap` ends the region under its current name and starts the
    next one in the same frame — a chain of back-to-back stages inside
    one ``with``.  ``name`` may be reassigned before exit (a kernel call
    learns only afterwards that it compiled)."""

    __slots__ = ("name", "arg", "counter", "root", "t0", "dur", "_child",
                 "_acct", "_staged", "_cpu", "_cpu_mark", "_ann", "_own_ctx")

    def __init__(self, name: str, arg=None, counter=None,
                 root: bool = False):
        self.name = name
        self.arg = arg
        self.counter = counter
        self.root = root
        self.dur = 0.0

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._own_ctx = False
        if self.root:
            if stack:
                self.root = False   # only the outermost root is one
            elif getattr(_tls, "ctx", 0) == 0:
                _tls.ctx = new_ctx()
                self._own_ctx = True
        self._open()
        now_cpu = time.thread_time()
        if stack:
            parent = stack[-1]
            parent._cpu += now_cpu - parent._cpu_mark
        stack.append(self)
        self._cpu_mark = now_cpu
        self.t0 = time.perf_counter()
        return self

    def _open(self) -> None:
        # _child: inclusive seconds of direct children (self = dur - it);
        # _acct: seconds inside this region that descendants already
        # charged to the query family; _staged: seconds that descendants
        # charged to counters of their own
        self._child = self._acct = self._staged = self._cpu = 0.0
        ann = None
        if _annotator is not None and _ENABLED:
            ann = _annotator("vm:" + self.name)
            ann.__enter__()
        self._ann = ann

    def lap(self, name: str, counter=None) -> None:
        """End this region now and start the next, named `name`, at the
        same instant and depth."""
        now = time.perf_counter()
        now_cpu = time.thread_time()
        stack = _tls.stack
        self._cpu += now_cpu - self._cpu_mark
        self._close(now, stack[-2] if len(stack) > 1 else None,
                    len(stack), stack[0].root)
        self.name = name
        self.counter = counter
        self._open()
        self._cpu_mark = now_cpu
        self.t0 = now

    def __exit__(self, *exc):
        now = time.perf_counter()
        now_cpu = time.thread_time()
        stack = _tls.stack
        # a child left open by a generator abandoned mid-phase must not
        # outlive its parent on the stack
        while stack and stack.pop() is not self:
            pass
        self._cpu += now_cpu - self._cpu_mark
        if stack:
            self._close(now, stack[-1], len(stack) + 1, stack[0].root)
            stack[-1]._cpu_mark = now_cpu
            return False
        self._close(now, None, 1, self.root)
        if self.root:
            _QUERY_WALL.inc(self.dur)
            if self._own_ctx:
                _tls.ctx = 0
        return False

    def _close(self, now: float, parent, depth: int, rooted: bool) -> None:
        """Record [t0, now) everywhere.  `rooted`: this thread holds a
        request root, so the query family is charged."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        name = self.name
        dur = self.dur = now - self.t0
        rec(name, self.t0, dur, self.arg, depth)
        tr = costacc.current()
        if tr is not None:
            tr.lap(name, dur - self._child, self._cpu)
        if parent is not None:
            parent._child += dur
        if self.counter is not None:
            # a family of its own: the time no descendant charged stays
            # with the enclosing query phase
            self.counter.inc(max(dur - self._staged, 0.0))
            if parent is not None:
                parent._acct += self._acct
                parent._staged += dur
            return
        if parent is not None:
            parent._acct += dur
            parent._staged += self._staged
        if rooted:
            c = _QUERY_PHASE.get(name)
            if c is None:
                # benign double-create: REGISTRY.float_counter dedups by
                # name, so two racing fills store the same object
                c = _QUERY_PHASE[name] = _query_phase_counter(name)  # vmt: disable=VMT015
            c.inc(max(dur - self._acct, 0.0))


# -- flight context (cross-thread query attribution) --------------------------

def new_ctx() -> int:
    """Fresh nonzero context id for one query/refresh."""
    with _ctx_lock:
        _ctx_counter[0] += 1
        return _ctx_counter[0]


def set_ctx(ctx: int) -> int:
    """Install `ctx` as the calling thread's flight context; returns the
    previous one (callers restore it).  utils/workpool calls this around
    each task with the submitter's ctx."""
    prev = getattr(_tls, "ctx", 0)
    _tls.ctx = ctx
    return prev


def get_ctx() -> int:
    return getattr(_tls, "ctx", 0)


def clear_ctx() -> None:
    _tls.ctx = 0


def note_capture(cap_id: int) -> None:
    """Thread-local hand-off: the serving layer notes the capture id a
    slow refresh just produced so the HTTP handler (same thread, outer
    frame) can attach it to the slow-query record."""
    _tls.noted_capture = cap_id


def take_noted_capture() -> int | None:
    cap_id = getattr(_tls, "noted_capture", None)
    _tls.noted_capture = None
    return cap_id


def ctx_events(ctx: int, window_s: float = 120.0) -> list[tuple]:
    """Every live ring event carrying `ctx`, merged across threads and
    sorted by t0: (t0, dur, name, tid).  The slow-query log uses this to
    compute a per-phase split for ONE query even though the phase spans
    ran on several pool workers."""
    if ctx == 0:
        return []
    min_t0 = time.perf_counter() - window_s
    with _rings_lock:
        rings = list(_rings)
    out = []
    for ring in rings:
        for t0, dur, name, c, _arg, _k, _depth in ring.snapshot(min_t0):
            if c == ctx:
                out.append((t0, dur, name, ring.tid))
    out.sort(key=lambda e: e[0])
    return out


def self_times(snap: list[tuple]) -> list[float]:
    """Self seconds of each event of ONE ring's snapshot: a phase's
    duration less its direct child phases' (recomputed offline from the
    nesting depth — children complete, and so are recorded, before
    their parent).  Plain recs (depth 0) keep their whole duration."""
    out = []
    pending: dict[int, float] = {}  # depth -> unclaimed child seconds
    for ev in snap:
        dur, depth = ev[1], ev[6]
        if depth == 0:
            out.append(dur)
            continue
        out.append(max(dur - pending.pop(depth + 1, 0.0), 0.0))
        pending[depth] = pending.get(depth, 0.0) + dur
    return out


def phase_split(ctx: int, window_s: float = 120.0) -> dict[str, float]:
    """Per-name span seconds for one flight context (the slow-query
    log's per-phase split), summed across every thread that worked on
    the query.  Phases count their SELF time, so nested phases stay
    disjoint."""
    if ctx == 0:
        return {}
    min_t0 = time.perf_counter() - window_s
    with _rings_lock:
        rings = list(_rings)
    split: dict[str, float] = {}
    for ring in rings:
        snap = ring.snapshot(min_t0)
        for ev, self_s in zip(snap, self_times(snap)):
            if ev[3] == ctx and ev[1] > 0.0:
                split[ev[2]] = split.get(ev[2], 0.0) + self_s
    return split


# -- capture ------------------------------------------------------------------

class FlightRecorder:
    """Owner of the bounded capture ring.  One process-wide instance
    (:data:`RECORDER`); tests may build private ones (they share the
    thread rings — captures differ only in their retention ring)."""

    def __init__(self, max_captures: int | None = None):
        if max_captures is None:
            try:
                max_captures = int(os.environ.get("VM_FLIGHT_CAPTURES", "8"))
            except ValueError:
                max_captures = 8
        import collections
        self._lock = threading.Lock()
        # builds serialize on their own lock so a serving-path
        # capture(defer_build=True) — which only needs _lock for the
        # id/append — never stalls behind a retrieval building traces
        self._build_lock = threading.Lock()
        self._captures: "collections.deque[dict]" = collections.deque(
            maxlen=max(max_captures, 1))
        self._next_id = 0
        self._captures_total = metricslib.REGISTRY.counter(
            "vm_flight_captures_total")
        self._dropped_total = metricslib.REGISTRY.counter(
            "vm_flight_dropped_events_total")

    # .. capture ..............................................................

    def capture(self, reason: str, window_s: float | None = None,
                meta: dict | None = None,
                defer_build: bool = False) -> dict | None:
        """Merge the live window of every thread ring into one Chrome
        trace-event JSON object and retain it.  Returns the capture
        record (meta + ``"trace"``), or None when the recorder is off.

        ``defer_build=True`` (the slow-refresh trigger path) does only
        the part that races the writers — snapshotting the rings — and
        postpones building the trace dicts and attribution summary until
        first retrieval, so the cost charged to the slow refresh itself
        (and to the latency its trigger is measuring — the observer
        effect) is the raw slot copy, not the JSON assembly."""
        if not _ENABLED:
            return None
        if window_s is None:
            window_s = _prune_window_s()
        now = time.perf_counter()
        min_t0 = now - window_s
        with _rings_lock:
            # reclaim dead-thread rings past the RETENTION window (not
            # this capture's, which may be narrower)
            _prune_dead_rings(
                now - max(window_s, _prune_window_s()))
            rings = list(_rings)
        snaps = []
        dropped = 0
        for ring in rings:
            snap = ring.snapshot(min_t0)
            # overwritten-before-capture accounting: cursor positions
            # below (i - cap) that no capture ever included are gone.
            # ring.taken is only ever touched by captures — serialize
            # the read-modify-write under _rings_lock so two concurrent
            # captures can't double-count the same lost events
            with _rings_lock:
                lost_floor = ring.i - ring.cap
                if lost_floor > ring.taken:
                    dropped += lost_floor - ring.taken
                    ring.taken = lost_floor
                if snap:
                    # first-uncaptured, hence the +1: snap[-1][5] itself
                    # WAS captured — counting it as lost on the next
                    # wrap would report drops on a lossless system
                    ring.taken = max(ring.taken, snap[-1][5] + 1)
            if snap:
                # tid/tname, not the ring itself: holding the ring would
                # keep a dead thread's slot arrays alive past the prune
                snaps.append((ring.tid, ring.tname, snap))
        if dropped:
            self._dropped_total.inc(dropped)
        from . import fasttime
        cap = {
            "reason": reason,
            "unix_ms": fasttime.unix_ms(),
            "window_s": window_s,
            "n_events": sum(len(s) for _t, _n, s in snaps),
            "n_threads": len(snaps),
            "_raw": (snaps, now),
        }
        if meta:
            cap.update(meta)
        with self._lock:
            self._next_id += 1
            cap["id"] = self._next_id
            self._captures.append(cap)
        self._captures_total.inc()
        if not defer_build:
            self._build(cap)
        return cap

    def _build(self, cap: dict) -> None:
        """Turn a capture's raw ring snapshots into ``cap["trace"]`` +
        ``cap["summary"]`` (idempotent; concurrent retrievals serialize
        on the build lock, so the loser waits and then sees the winner's
        finished build instead of a half-written capture)."""
        with self._build_lock:
            raw = cap.pop("_raw", None)
            if raw is None:
                return
            snaps, now = raw
            # trace timestamps are µs relative to the window start, so
            # the Perfetto timeline starts at ~0 regardless of process
            # uptime.  Global min over ALL events: rings are in
            # COMPLETION order (spans record at end time), so a ring's
            # first entry is not its earliest t0 — an enclosing span
            # lands after its children and would otherwise get a
            # negative ts
            epoch = min((e[0] for _tid, _tn, snap in snaps for e in snap),
                        default=now)
            trace_events = []
            pid = os.getpid()
            for tid, tname, snap in snaps:
                trace_events.append({
                    "name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": tname}})
                for t0, dur, name, ctx, arg, _k, depth in snap:
                    ev = {"name": name, "ph": "X", "pid": pid, "tid": tid,
                          "ts": round((t0 - epoch) * 1e6, 1),
                          "dur": round(dur * 1e6, 1)}
                    if dur == 0.0:
                        ev["ph"] = "i"
                        ev["s"] = "t"
                        del ev["dur"]
                    args = {}
                    if ctx:
                        args["ctx"] = ctx
                    if arg is not None:
                        args["arg"] = arg
                    if depth:
                        args["depth"] = depth
                    if args:
                        ev["args"] = args
                    trace_events.append(ev)
            trace_events.sort(key=lambda e: e.get("ts", 0.0))
            cap["trace"] = {"traceEvents": trace_events,
                            "displayTimeUnit": "ms"}
            cap["summary"] = summarize(
                trace_events, focus_ctx=cap.get("ctx", 0))

    # .. retrieval ............................................................

    def total(self) -> int:
        """Monotonic count of captures ever taken (ids are 1..total);
        unlike ``len(list())`` it is not bounded by the retention ring."""
        with self._lock:
            return self._next_id

    def list(self) -> list[dict]:
        """Capture metadata, newest first (everything but the trace)."""
        with self._lock:
            caps = list(self._captures)
        for c in caps:
            self._build(c)
        return [{k: v for k, v in c.items() if k != "trace"}
                for c in reversed(caps)]

    def get(self, cap_id: int) -> dict | None:
        with self._lock:
            found = None
            for c in self._captures:
                if c["id"] == cap_id:
                    found = c
                    break
        if found is not None:
            self._build(found)
        return found

    def clear(self) -> None:
        with self._lock:
            self._captures.clear()


def summarize(trace_events: list[dict], focus_ctx: int = 0) -> dict:
    """Attribution summary of one capture: total span ms by event name,
    plus — when the capture contains serve:refresh spans — the slowest
    refresh and the background work overlapping it by category (the
    "which work overlapped the slow refresh" answer, precomputed so the
    JSON artifact and the HTTP list are readable without Perfetto).

    `focus_ctx` pins WHICH refresh gets the overlap treatment: a
    slow-refresh-triggered capture passes the triggering refresh's
    flight context so the summary explains THAT refresh, not whatever
    bigger serve span (e.g. the cold first eval) shares the window.
    0 (on-demand captures) falls back to the slowest serve span."""
    by_name: dict[str, float] = {}
    serves = []
    for ev in trace_events:
        if ev["ph"] != "X":
            continue
        dur = ev.get("dur", 0.0)
        by_name[ev["name"]] = by_name.get(ev["name"], 0.0) + dur
        if ev["name"] == "serve:refresh":
            serves.append(ev)
    out = {"span_ms_by_name": {k: round(v / 1e3, 3)
                               for k, v in sorted(by_name.items())}}
    if focus_ctx:
        focused = [e for e in serves
                   if e.get("args", {}).get("ctx", 0) == focus_ctx]
        serves = focused or serves
    if serves:
        slow = max(serves, key=lambda e: e["dur"])
        s0, s1 = slow["ts"], slow["ts"] + slow["dur"]
        sctx = slow.get("args", {}).get("ctx", 0)
        overlap: dict[str, list] = {}
        waiting: dict[str, list] = {}
        for ev in trace_events:
            if ev["ph"] != "X" or ev is slow:
                continue
            # overlap of [ts, ts+dur) with the slow serve window,
            # excluding the serve's own work (same ctx) — what's left is
            # the INTERFERING work the refresh had to share cores with.
            # ctx-only, NOT tid: ambient work that ran ON the serve
            # thread (a gc pause, a foreign pool task the blocked serve
            # thread helped with) carries ctx 0 / another ctx and IS
            # part of the latency story
            if ev.get("args", {}).get("ctx", 0) == sctx:
                continue
            lo = max(ev["ts"], s0)
            hi = min(ev["ts"] + ev.get("dur", 0.0), s1)
            if hi <= lo:
                continue
            name = ev["name"]
            # pure waits are DEFERENCE, not interference: a merge
            # sleeping in the serve-priority yield (or queued at a gate)
            # consumed no CPU during the refresh — charging it as
            # "merge overlap" would invert the attribution.  Reported
            # separately so the deference is still visible.  (lock:*
            # waits stay in the overlap buckets: a thread stalled on a
            # lock a serve-path thread holds IS part of the story.)
            if name.endswith((":queue_wait", ":gate_wait", ":yield")):
                waiting.setdefault(name, []).append((lo, hi))
                continue
            cat = name.split(":", 1)[0]
            overlap.setdefault(cat, []).append((lo, hi))
        # interval UNION per bucket, not a sum: nested spans (the
        # flush:table fan span contains its workers' flush:part spans)
        # and repeated waits would otherwise report more overlap than
        # the refresh's own duration.  The number is wall-clock coverage
        # ("merge work was running for X of the refresh's Y ms"), not
        # cpu-seconds.
        out["slow_refresh"] = {
            "ms": round(slow["dur"] / 1e3, 3),
            "ctx": sctx,
            "arg": slow.get("args", {}).get("arg"),
            "overlap_ms_by_category": {
                k: round(_union(v) / 1e3, 3)
                for k, v in sorted(overlap.items())},
            "waiting_ms_by_name": {
                k: round(_union(v) / 1e3, 3)
                for k, v in sorted(waiting.items())},
        }
    return out


def _union(intervals: list) -> float:
    """Total length of the union of [lo, hi) intervals."""
    total = 0.0
    cur_lo = cur_hi = None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


#: the process-wide recorder behind /api/v1/status/flight
RECORDER = FlightRecorder()


def slow_refresh_threshold_ms() -> float:
    """``VM_SLOW_REFRESH_MS``: refreshes slower than this trigger a
    flight capture on the serving path (0 disables the trigger; the
    default 1000ms only fires on genuinely pathological refreshes)."""
    try:
        return float(os.environ.get("VM_SLOW_REFRESH_MS", "1000"))
    except ValueError:
        return 1000.0


# -- gc visibility ------------------------------------------------------------

def _gc_hook(t0: float, dur: float, gen) -> None:
    # gc callbacks fire on whatever thread triggered the collection —
    # possibly INSIDE rec()'s slot stores, or inside a _rings_lock
    # critical section (ring creation / capture allocate).  Recording
    # would then tear the in-progress slot or self-deadlock taking the
    # non-reentrant lock from _new_ring, so: only record when this
    # thread already owns a ring and is not mid-record.  (A nested
    # collection can't fire inside THIS rec — gc suppresses reentrant
    # collections while callbacks run.)
    if not _ENABLED:
        return
    ring = getattr(_tls, "ring", None)
    if ring is None or ring.w == ring.i:
        return
    # ctx 0, not the thread's current query ctx: a gc pause is ambient
    # process work, and charging it to the query would hide it from the
    # capture summary's interference buckets (own-ctx work is excluded)
    prev = getattr(_tls, "ctx", 0)
    _tls.ctx = 0
    try:
        rec(f"gc:gen{gen}", t0, dur)
    finally:
        _tls.ctx = prev


def install_gc_events() -> None:
    """Record every gc collection as a flight span on the thread that
    triggered it (gc pauses are a serving-latency suspect).  Piggybacks
    on utils/metrics' single gc callback — the one timing of each
    collection feeds both vm_gc_pause_seconds_total and the timeline."""
    if _gc_hook not in metricslib.gc_pause_hooks:
        metricslib.gc_pause_hooks.append(_gc_hook)


install_gc_events()
