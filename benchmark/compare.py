"""The comparison that decides `correct`: one served answer against the
plain reference's.  Returns the numbers compared; run.py holds each to
its limit.

* rel_err: the widest |served - reference| / max(|reference|, FLOOR) over
  every value of the answer.  For a topk it also counts how far the
  smallest value the server chose lies below the reference's k-th largest
  (near-ties may fall either way under f32; a wrong choice reads large).
* series_mismatch: series served but not in the reference, or the reverse.
* nan_mismatch: grid steps where one side has a value and the other none
  (a fresh scrape that the answer does not show counts here); for a topk,
  steps where the server chose another number of series than k.
"""

from __future__ import annotations

import json

import numpy as np

FLOOR = 1e-3


def labels_key(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def parse_answer(body: bytes, start_ms: int, end_ms: int, step_ms: int):
    """query_range JSON -> (ok, {labels_key: [T] float64, NaN where
    absent}).  ok is False for an error, a partial answer or a value off
    the grid."""
    try:
        doc = json.loads(body)
    except ValueError:
        return False, {}
    if doc.get("status") != "success" or doc.get("isPartial"):
        return False, {}
    n = (end_ms - start_ms) // step_ms + 1
    out = {}
    for r in doc["data"]["result"]:
        row = np.full(n, np.nan)
        for t, v in r["values"]:
            at = (round(t * 1000) - start_ms) / step_ms
            if at != int(at) or not 0 <= at < n:
                return False, {}
            row[int(at)] = float(v)
        out[labels_key(r["metric"])] = row
    return True, out


def _rel(got, want):
    return np.abs(got - want) / np.maximum(np.abs(want), FLOOR)


def compare(kind: str, got: dict, ref_labels: list, ref: np.ndarray) -> dict:
    """One answer's numbers.  `kind`, `ref_labels`, `ref` are what
    reference.evaluate returned."""
    want = {labels_key(l): ref[i] for i, l in enumerate(ref_labels)}
    if kind.startswith("topk:"):
        return _compare_topk(int(kind[5:]), got, want, ref)
    want = {k: v for k, v in want.items() if not np.isnan(v).all()}
    out = {"rel_err": 0.0, "nan_mismatch": 0, "values": 0,
           "series_mismatch": len(set(got) ^ set(want))}
    for k in set(got) & set(want):
        g, w = got[k], want[k]
        out["nan_mismatch"] += int((np.isnan(g) != np.isnan(w)).sum())
        m = ~np.isnan(g) & ~np.isnan(w)
        out["values"] += int(m.sum())
        if m.any():
            out["rel_err"] = max(out["rel_err"], float(_rel(g[m], w[m]).max()))
    return out


def _compare_topk(k: int, got: dict, want: dict, ref: np.ndarray) -> dict:
    out = {"rel_err": 0.0, "nan_mismatch": 0, "values": 0,
           "series_mismatch": len(set(got) - set(want))}
    keys = [key for key in got if key in want]
    if not keys:
        out["series_mismatch"] += 1
        return out
    g = np.stack([got[key] for key in keys])
    w = np.stack([want[key] for key in keys])
    chosen = ~np.isnan(g)
    out["values"] = int(chosen.sum())
    # a chosen value has to be that series' own value ...
    bad = chosen & np.isnan(w)
    out["nan_mismatch"] += int(bad.sum())
    m = chosen & ~bad
    if m.any():
        out["rel_err"] = float(_rel(g[m], w[m]).max())
    # ... as many are chosen as the reference has to give, up to k ...
    due = np.minimum(k, (~np.isnan(ref)).sum(axis=0))
    out["nan_mismatch"] += int((chosen.sum(axis=0) != due).sum())
    # ... and none lies below the reference's k-th largest
    filled = np.where(np.isnan(ref), -np.inf, ref)
    kth = np.sort(filled, axis=0)[::-1][np.maximum(due, 1) - 1,
                                        np.arange(ref.shape[1])]
    low = np.where(m, w, np.inf).min(axis=0)
    t = np.isfinite(low) & np.isfinite(kth)
    if t.any():
        gap = np.maximum(kth[t] - low[t], 0) / np.maximum(np.abs(kth[t]), FLOOR)
        out["rel_err"] = max(out["rel_err"], float(gap.max()))
    return out


def worst(per_answer: list) -> dict:
    """The widest of each number over the checked answers."""
    out = {"rel_err": 0.0, "series_mismatch": 0, "nan_mismatch": 0,
           "values": 0}
    for r in per_answer:
        out["rel_err"] = max(out["rel_err"], r["rel_err"])
        for k in ("series_mismatch", "nan_mismatch", "values"):
            out[k] += r[k]
    return out
