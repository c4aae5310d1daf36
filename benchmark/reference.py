"""The plain reference: the MetricsQL subset the cells ask, evaluated in
float64 NumPy straight from the samples the benchmark generated.  It
imports nothing of the program and takes nothing the program made: its
input is the deployment's own arrays (labels, timestamps, values).

Semantics follow upstream VictoriaMetrics (app/vmselect/promql/rollup.go,
aggr.go and transform.go):

* a selector may leave the metric name out, and `=~` on any label,
  `__name__` among them, matches the WHOLE value, as Prometheus anchors
  it (a label the series lacks is the empty string);
* a rollup at grid time t sees the window (t - w, t];
* `rate` is (last - prev) / dt, prev being the last sample at or before
  the window start when it lies within maxPrevInterval of it, else the
  window's first sample (two samples needed); counter resets are removed;
* `avg_over_time` is the mean of the window's samples, NaN on an empty
  window;
* `max_over_time` and `avg_over_time` keep the metric name (upstream's
  keep-name list), `rate` drops it;
* `sum`, `max` and `avg by (l)` ignore NaN and are NaN where every member
  is; the `by (...)` clause stands before the argument or after it, and
  `__name__` is a grouping label where the rollup kept the name;
* `topk(k, x)` keeps, at each t, the k largest x;
* `histogram_quantile(phi, buckets)` interpolates linearly inside the
  bucket that holds rank phi * total, the lowest bucket starting at 0 and
  the +Inf bucket answering the highest finite bound.

`dedup` is upstream's deduplication (lib/storage/dedup.go, recalled), for a
configuration that states `-dedup.minScrapeInterval`: of one series' sorted
samples, one survivor a window of the interval, windows right-inclusive at
exact multiples of it; `dedup_rows` makes the dense rows `evaluate` reads
from the survivors.

`round_rollup` is the CONTROL's hook: a function applied to every rollup
output before anything else sees it (the control rounds it to bfloat16,
what the MXU's default precision does to the operands of the group sum).
"""

from __future__ import annotations

import re

import numpy as np

_TOKEN = re.compile(
    r'\s*([A-Za-z_:][A-Za-z0-9_:]*|[0-9.]+[smhd]?|"[^"]*"|=~|[(){}\[\],=])')
_DUR = {"s": 1000, "m": 60_000, "h": 3_600_000, "d": 86_400_000}
ROLLUPS = ("rate", "max_over_time", "avg_over_time")
KEEP_NAME = ("max_over_time", "avg_over_time")
AGGREGATES = ("sum", "max", "avg")


def parse(expr: str):
    """Query text -> nested tuples:
    ("hq", phi, e) | ("sum" | "max" | "avg", by, e) | ("topk", k, e) |
    ("rollup", func, name, {label: matcher}, window_ms), where name is
    None for a selector without one and a matcher is the value itself
    for `=`, ("=~", pattern) for `=~`."""
    toks, pos = [], 0
    while pos < len(expr):
        m = _TOKEN.match(expr, pos)
        if not m:
            if expr[pos:].strip() == "":
                break
            raise ValueError(f"cannot read {expr[pos:]!r} in {expr!r}")
        toks.append(m.group(1))
        pos = m.end()
    ast, rest = _expr(toks)
    if rest:
        raise ValueError(f"trailing {rest} in {expr!r}")
    return ast


def _eat(toks, want):
    if not toks or toks[0] != want:
        raise ValueError(f"expected {want!r}, found {toks[:1]}")
    return toks[1:]


def _by(toks):
    """An optional `by (a, b)` at the head of toks -> (labels, rest)."""
    if toks[:1] != ["by"]:
        return None, toks
    by, toks = [], _eat(toks[1:], "(")
    while toks[0] != ")":
        if toks[0] != ",":
            by.append(toks[0])
        toks = toks[1:]
    return tuple(by), toks[1:]


def _selector(toks):
    """`name`, `name{...}` or `{...}` -> (name, matchers, rest)."""
    name = None
    if toks[0] != "{":
        name, toks = toks[0], toks[1:]
    matchers = {}
    if toks[0] == "{":
        toks = toks[1:]
        while toks[0] != "}":
            if toks[0] == ",":
                toks = toks[1:]
                continue
            label, op, value = toks[0], toks[1], toks[2].strip('"')
            if op not in ("=", "=~"):
                raise ValueError(f"expected '=' or '=~', found {op!r}")
            matchers[label] = value if op == "=" else (op, value)
            toks = toks[3:]
        toks = toks[1:]
    return name, matchers, toks


def _expr(toks):
    head, toks = toks[0], toks[1:]
    if head in AGGREGATES:
        by, toks = _by(toks)
        toks = _eat(toks, "(")
        sub, toks = _expr(toks)
        toks = _eat(toks, ")")
        if by is None:
            by, toks = _by(toks)
        return (head, by or (), sub), toks
    if head in ("topk", "histogram_quantile"):
        toks = _eat(toks, "(")
        num, toks = float(toks[0]), _eat(toks[1:], ",")
        sub, toks = _expr(toks)
        kind = ("topk", int(num)) if head == "topk" else ("hq", num)
        return kind + (sub,), _eat(toks, ")")
    if head in ROLLUPS:
        name, matchers, toks = _selector(_eat(toks, "("))
        toks = _eat(toks, "[")
        window = int(float(toks[0][:-1]) * _DUR[toks[0][-1]])
        toks = _eat(_eat(toks[1:], "]"), ")")
        return ("rollup", head, name, matchers, window), toks
    raise ValueError(f"the reference does not know {head!r}")


def _rollup_of(ast):
    """The one rollup node under `ast`."""
    while ast[0] != "rollup":
        ast = ast[-1]
    return ast


def selector(ast):
    """The (name, matchers) of the one selector under `ast`."""
    return _rollup_of(ast)[2:4]


def window_of(ast) -> int:
    """The lookbehind window, in ms, of the one rollup under `ast`."""
    return _rollup_of(ast)[4]


def select(labels: list, name, matchers: dict) -> np.ndarray:
    """Row indices of the series the selector matches.  A matcher is
    decided once for each distinct value of its label, not once a
    series."""
    if name is not None:
        matchers = dict(matchers, __name__=name)
    keep = np.ones(len(labels), dtype=bool)
    for label, want in matchers.items():
        values = [l.get(label, "") for l in labels]
        if isinstance(want, tuple):
            rx = re.compile(want[1])
            ok = {v for v in set(values) if rx.fullmatch(v)}
        else:
            ok = {want}
        keep &= np.fromiter((v in ok for v in values), bool, len(labels))
    return np.flatnonzero(keep).astype(np.int64)


def by_groups(by: tuple, labels: list) -> dict:
    """{group's labels as ((label, value), ...): [rows]} of `... by (by)`,
    in order of first appearance."""
    groups = {}
    for i, l in enumerate(labels):
        groups.setdefault(tuple((k, l[k]) for k in by if k in l),
                          []).append(i)
    return groups


def le_groups(labels: list) -> dict:
    """{what is left of a bucket row's labels without `le` and the name:
    [(le, row)]}: the groups histogram_quantile answers one row for."""
    groups = {}
    for i, l in enumerate(labels):
        rest = tuple(sorted((k, v) for k, v in l.items()
                            if k not in ("le", "__name__")))
        groups.setdefault(rest, []).append((float(l["le"]), i))
    return groups


def _rollup_labels(func: str, labels: list, idx) -> list:
    keep = func in KEEP_NAME
    return [{k: v for k, v in labels[i].items() if keep or k != "__name__"}
            for i in idx]


def row_labels(ast, labels: list) -> list:
    """The label set of every row `ast` can answer (all the candidates
    of a topk), without a sample read: what `evaluate` returns as its
    labels."""
    op = ast[0]
    if op == "rollup":
        return _rollup_labels(ast[1], labels, select(labels, ast[2], ast[3]))
    sub = row_labels(ast[-1], labels)
    if op in AGGREGATES:
        return [dict(k) for k in by_groups(ast[1], sub)]
    if op == "hq":
        return [dict(k) for k in le_groups(sub)]
    return sub


def _counts_le(ts: np.ndarray, marks: np.ndarray) -> np.ndarray:
    """[S, T]: how many of row s's sorted timestamps are <= marks[t]."""
    n_rows, n = ts.shape
    off = (np.arange(n_rows, dtype=np.int64) << 42)[:, None]
    flat = (ts + off).ravel()
    at = np.searchsorted(flat, (marks[None, :] + off).ravel(), side="right")
    return at.reshape(n_rows, -1) - np.arange(n_rows)[:, None] * n


def _max_prev_interval(ts: np.ndarray) -> np.ndarray:
    """[S]: upstream's jitter headroom over the scrape interval, itself
    the 0.6 quantile of the last 20 intervals."""
    si = np.quantile(np.diff(ts[:, -21:], axis=1).astype(np.float64),
                     0.6, axis=1).astype(np.int64)
    shift = np.select([si <= 2000, si <= 4000, si <= 8000, si <= 16000,
                       si <= 32000], [si * 4, si * 2, si, si // 2, si // 4],
                      si // 8)
    return si + shift


def _remove_resets(v: np.ndarray) -> np.ndarray:
    d = np.diff(v, axis=1)
    prev = v[:, :-1]
    drop = np.where(d < 0, np.where(-d * 8 < prev, -d, prev), 0.0)
    return v + np.concatenate([np.zeros((v.shape[0], 1)),
                               np.cumsum(drop, axis=1)], axis=1)


def rollup(func: str, ts: np.ndarray, vals: np.ndarray, grid: np.ndarray,
           window: int) -> np.ndarray:
    """[S, T] float64; NaN where the window is empty."""
    vals = vals.astype(np.float64)
    hi = _counts_le(ts, grid)
    lo = _counts_le(ts, grid - window)
    have = hi > lo
    rows = np.arange(ts.shape[0])[:, None]
    last = np.maximum(hi - 1, 0)
    if func in ("max_over_time", "avg_over_time"):
        # the window's samples one at a time, oldest first: a plain loop's
        # own order of summation
        fold, out = (np.maximum, np.full(hi.shape, -np.inf)) \
            if func == "max_over_time" else (np.add, np.zeros(hi.shape))
        for k in range(int((hi - lo).max(initial=0))):
            at = lo + k
            ok = at < hi
            out = np.where(ok, fold(
                out, vals[rows, np.minimum(at, ts.shape[1] - 1)]), out)
        if func == "avg_over_time":
            out = out / np.maximum(hi - lo, 1)
        return np.where(have, out, np.nan)
    if func == "rate":
        v = _remove_resets(vals)
        prev = np.maximum(lo - 1, 0)
        mpi = _max_prev_interval(ts)[:, None]
        gated = (lo > 0) & (ts[rows, prev] > grid[None, :] - window - mpi)
        first = np.where(gated, prev, np.minimum(lo, ts.shape[1] - 1))
        ok = have & (gated | (hi - lo >= 2))
        dt = (ts[rows, last] - ts[rows, first]) / 1e3
        dv = v[rows, last] - v[rows, first]
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(ok & (dt > 0), dv / dt, np.nan)
    raise ValueError(f"the reference does not know {func!r}")


def _aggregate(op: str, x: np.ndarray) -> np.ndarray:
    """[M, T] -> [T]: members that are NaN at a step do not count there,
    and a step at which every member is NaN answers NaN."""
    n = (~np.isnan(x)).sum(axis=0)
    if op == "max":
        out = np.where(np.isnan(x), -np.inf, x).max(axis=0)
    else:
        out = np.nansum(x, axis=0)
        if op == "avg":
            out = out / np.maximum(n, 1)
    return np.where(n > 0, out, np.nan)


def _histogram_quantile(phi: float, les: np.ndarray, m: np.ndarray
                        ) -> np.ndarray:
    """les sorted ascending, m [B, T] cumulative counts."""
    out = np.full(m.shape[1], np.nan)
    for j in range(m.shape[1]):
        c = m[:, j]
        if np.isnan(c).all():
            continue
        c = np.maximum.accumulate(np.nan_to_num(c))
        if c[-1] == 0:
            continue
        rank = phi * c[-1]
        i = min(int(np.searchsorted(c, rank, side="left")), les.size - 1)
        if np.isinf(les[i]):
            out[j] = les[i - 1] if i > 0 else np.nan
            continue
        lo, c_lo = (les[i - 1], c[i - 1]) if i > 0 else (0.0, 0.0)
        out[j] = les[i] if c[i] <= c_lo else \
            lo + (les[i] - lo) * (rank - c_lo) / (c[i] - c_lo)
    return out


def evaluate(ast, labels: list, ts: np.ndarray, vals: np.ndarray,
             grid: np.ndarray, round_rollup=None):
    """-> (kind, [label dict per row], [R, T] float64).  kind is "topk:k"
    for a topk (rows are then ALL candidates: the comparison chooses,
    because near-ties may fall either way), else "rows"."""
    op = ast[0]
    if op == "rollup":
        _, func, name, matchers, window = ast
        idx = select(labels, name, matchers)
        out = rollup(func, ts[idx], vals[idx], grid, window)
        if round_rollup is not None:
            out = round_rollup(out)
        return "rows", _rollup_labels(func, labels, idx), out
    kind, sub_labels, sub = evaluate(ast[-1], labels, ts, vals, grid,
                                     round_rollup)
    if kind != "rows":
        raise ValueError("the reference nests nothing over topk")
    if op in AGGREGATES:
        groups = by_groups(ast[1], sub_labels)
        return "rows", [dict(k) for k in groups], \
            np.stack([_aggregate(op, sub[rows]) for rows in groups.values()])
    if op == "topk":
        return f"topk:{ast[1]}", sub_labels, sub
    if op == "hq":
        groups = le_groups(sub_labels)
        keys = list(groups)
        rows = []
        for k in keys:
            members = sorted(groups[k])
            rows.append(_histogram_quantile(
                ast[1], np.array([le for le, _ in members]),
                sub[[i for _, i in members]]))
        return "rows", [dict(k) for k in keys], np.stack(rows)
    raise ValueError(f"the reference does not know {op!r}")


def newest_of_window(ts: np.ndarray, interval_ms: int) -> np.ndarray:
    """Sorted rows [..., N] -> bool [..., N]: the places that hold their
    dedup window's newest sample (of equal timestamps the last place).  A
    sample belongs to window ceil(ts / interval)."""
    window = -(-ts // interval_ms)
    out = np.ones(ts.shape, dtype=bool)
    out[..., :-1] = window[..., 1:] != window[..., :-1]
    return out


def dedup(ts: np.ndarray, vals: np.ndarray, interval_ms: int):
    """One row's sorted samples -> its survivors (ts, vals).  A sample
    belongs to window ceil(ts / interval): the window (m - 1, m] x
    interval, so a sample exactly on a multiple closes its own.  Of a
    window the sample with the highest timestamp stays; of several at
    that timestamp, the largest value.  (No staleness marker occurs in
    the benchmark's data; upstream prefers any value over one.)"""
    ts = np.asarray(ts, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float64)
    if ts.size == 0:
        return ts, vals
    last = np.flatnonzero(newest_of_window(ts, interval_ms))
    out_ts, out_vals = ts[last], vals[last].copy()
    first = np.append(0, last[:-1] + 1)
    # a tie at a window's newest timestamp: rare, so one at a time
    for i in np.flatnonzero((last > first) & (ts[last] == ts[last - 1])):
        a, b = first[i], last[i] + 1
        out_vals[i] = vals[a:b][ts[a:b] == ts[b - 1]].max()
    return out_ts, out_vals


PAD_TS = 0


def dedup_rows(ts: np.ndarray, vals: np.ndarray, interval_ms: int):
    """[S, N] sorted rows -> ([S, M], [S, M]): every row's survivors,
    right-aligned, M the most any row keeps.  A shorter row is filled
    from the left with PAD_TS (older than any window a query reaches, and
    more than any maxPrevInterval under the first real sample) and the
    row's own oldest value (no reset, no increase): rows stay sorted and
    no rollup here ever reads a filled place."""
    rows = [dedup(t, v, interval_ms) for t, v in zip(ts, vals)]
    width = max(t.size for t, _ in rows)
    out_ts = np.full((len(rows), width), PAD_TS, dtype=np.int64)
    out_vals = np.empty((len(rows), width), dtype=np.float64)
    for i, (t, v) in enumerate(rows):
        out_ts[i, width - t.size:] = t
        out_vals[i, width - v.size:] = v
        out_vals[i, :width - v.size] = v[0] if v.size else 0.0
    return out_ts, out_vals


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """Round float64 to the nearest bfloat16 (8 significant bits),
    returned as float64: the control's precision."""
    f = np.asarray(x, dtype=np.float32)
    bits = f.view(np.uint32).astype(np.uint64)
    rounded = ((bits + 0x7FFF + ((bits >> 16) & 1)) >> 16) << 16
    out = rounded.astype(np.uint32).view(np.float32).astype(np.float64)
    return np.where(np.isnan(x), np.nan, out)
