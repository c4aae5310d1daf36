"""The plain reference: the MetricsQL subset the cells ask, evaluated in
float64 NumPy straight from the samples the benchmark generated.  It
imports nothing of the program and takes nothing the program made: its
input is the deployment's own arrays (labels, timestamps, values).

Semantics follow upstream VictoriaMetrics (app/vmselect/promql/rollup.go
and transform.go):

* a rollup at grid time t sees the window (t - w, t];
* `rate` is (last - prev) / dt, prev being the last sample at or before
  the window start when it lies within maxPrevInterval of it, else the
  window's first sample (two samples needed); counter resets are removed;
* `max_over_time` keeps the metric name, `rate` drops it;
* `sum by (l)` ignores NaN and is NaN where every member is;
* `topk(k, x)` keeps, at each t, the k largest x;
* `histogram_quantile(phi, buckets)` interpolates linearly inside the
  bucket that holds rank phi * total, the lowest bucket starting at 0 and
  the +Inf bucket answering the highest finite bound.

`round_rollup` is the CONTROL's hook: a function applied to every rollup
output before anything else sees it (the control rounds it to bfloat16,
what the MXU's default precision does to the operands of the group sum).
"""

from __future__ import annotations

import re

import numpy as np

_TOKEN = re.compile(r'\s*([A-Za-z_:][A-Za-z0-9_:]*|[0-9.]+[smhd]?|"[^"]*"|[(){}\[\],=])')
_DUR = {"s": 1000, "m": 60_000, "h": 3_600_000, "d": 86_400_000}
ROLLUPS = ("rate", "max_over_time")
KEEP_NAME = ("max_over_time",)


def parse(expr: str):
    """Query text -> nested tuples:
    ("hq", phi, e) | ("sum", by, e) | ("topk", k, e) |
    ("rollup", func, name, {label: value}, window_ms)."""
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


def _expr(toks):
    head, toks = toks[0], toks[1:]
    if head == "sum":
        by = []
        if toks[0] == "by":
            toks = _eat(toks[1:], "(")
            while toks[0] != ")":
                if toks[0] != ",":
                    by.append(toks[0])
                toks = toks[1:]
            toks = toks[1:]
        toks = _eat(toks, "(")
        sub, toks = _expr(toks)
        return ("sum", tuple(by), sub), _eat(toks, ")")
    if head in ("topk", "histogram_quantile"):
        toks = _eat(toks, "(")
        num, toks = float(toks[0]), _eat(toks[1:], ",")
        sub, toks = _expr(toks)
        kind = ("topk", int(num)) if head == "topk" else ("hq", num)
        return kind + (sub,), _eat(toks, ")")
    if head in ROLLUPS:
        toks = _eat(toks, "(")
        name, toks = toks[0], toks[1:]
        matchers = {}
        if toks[0] == "{":
            toks = toks[1:]
            while toks[0] != "}":
                if toks[0] == ",":
                    toks = toks[1:]
                    continue
                label, toks = toks[0], _eat(toks[1:], "=")
                matchers[label], toks = toks[0].strip('"'), toks[1:]
            toks = toks[1:]
        toks = _eat(toks, "[")
        window = int(float(toks[0][:-1]) * _DUR[toks[0][-1]])
        toks = _eat(_eat(toks[1:], "]"), ")")
        return ("rollup", head, name, matchers, window), toks
    raise ValueError(f"the reference does not know {head!r}")


def selector(ast):
    """The (name, matchers) of the one selector under `ast`."""
    while ast[0] != "rollup":
        ast = ast[-1]
    return ast[2], ast[3]


def select(labels: list, name: str, matchers: dict) -> np.ndarray:
    """Row indices of the series the selector matches."""
    return np.array([i for i, l in enumerate(labels)
                     if l["__name__"] == name and
                     all(l.get(k) == v for k, v in matchers.items())],
                    dtype=np.int64)


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
    if func == "max_over_time":
        out = np.full(hi.shape, -np.inf)
        for k in range(int((hi - lo).max(initial=0))):
            at = lo + k
            ok = at < hi
            out = np.where(ok, np.maximum(
                out, vals[rows, np.minimum(at, ts.shape[1] - 1)]), out)
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


def _nansum(x: np.ndarray) -> np.ndarray:
    some = ~np.isnan(x).all(axis=0)
    return np.where(some, np.nansum(x, axis=0), np.nan)


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
        keep = func in KEEP_NAME
        return "rows", [{k: v for k, v in labels[i].items()
                         if keep or k != "__name__"} for i in idx], out
    kind, sub_labels, sub = evaluate(ast[-1], labels, ts, vals, grid,
                                     round_rollup)
    if kind != "rows":
        raise ValueError("the reference nests nothing over topk")
    if op == "sum":
        groups = {}
        for i, l in enumerate(sub_labels):
            groups.setdefault(tuple((k, l[k]) for k in ast[1] if k in l),
                              []).append(i)
        keys = list(groups)
        return "rows", [dict(k) for k in keys], \
            np.stack([_nansum(sub[groups[k]]) for k in keys])
    if op == "topk":
        return f"topk:{ast[1]}", sub_labels, sub
    if op == "hq":
        groups = {}
        for i, l in enumerate(sub_labels):
            rest = tuple(sorted((k, v) for k, v in l.items()
                                if k not in ("le", "__name__")))
            groups.setdefault(rest, []).append((float(l["le"]), i))
        keys = list(groups)
        rows = []
        for k in keys:
            members = sorted(groups[k])
            rows.append(_histogram_quantile(
                ast[1], np.array([le for le, _ in members]),
                sub[[i for _, i in members]]))
        return "rows", [dict(k) for k in keys], np.stack(rows)
    raise ValueError(f"the reference does not know {op!r}")


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """Round float64 to the nearest bfloat16 (8 significant bits),
    returned as float64: the control's precision."""
    f = np.asarray(x, dtype=np.float32)
    bits = f.view(np.uint32).astype(np.uint64)
    rounded = ((bits + 0x7FFF + ((bits >> 16) & 1)) >> 16) << 16
    out = rounded.astype(np.uint32).view(np.float32).astype(np.float64)
    return np.where(np.isnan(x), np.nan, out)
