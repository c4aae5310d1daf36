"""The ``query_range`` matrix answer, from the evaluator's rows to the
response bytes.

``rows`` turns every row's float64 ``[T]`` values into the JSON text of
its ``values`` list in one native call over the stacked ``[R, T]`` block
(``native.write_matrix``: no Python object a point, and a large answer's
rows written by several threads at once); ``body`` joins those texts with
each row's ``metric`` object (``json.dumps``, once a name: a panel's rows
are the same rows every refresh) and the envelope, in a buffer kept from
one body to the next.  The bytes equal
``json.dumps`` of the whole answer as a tree of dicts and lists, default
separators included.  Where the native library is unavailable the Python
loop makes the same texts, as every routine of ``native`` falls back."""

from __future__ import annotations

import collections
import io
import json
import math

import numpy as np

from .. import native
from ..query.format_value import fmt_value
from ..utils import metrics as metricslib

#: points written into matrix answers by the writer that formatted them:
#: both registered at import, so a reader never has to tell "absent" from
#: "never engaged"
POINTS = {w: metricslib.REGISTRY.counter(
    f'vm_http_matrix_points_total{{writer="{w}"}}')
    for w in ("native", "python")}
#: of them, the points of answers whose rows the native call cut into more
#: than one range (written at once by the caller and its helper threads)
PARALLEL_POINTS = metricslib.REGISTRY.counter(
    "vm_http_matrix_parallel_points_total")
#: rows whose ``metric`` object ``body`` looked up in its memo
MEMO = {r: metricslib.REGISTRY.counter(
    f'vm_http_matrix_metric_memo_total{{result="{r}"}}')
    for r in ("hit", "miss")}

#: a ``MetricName``'s (group, labels in their order) -> its row's text up
#: to the values, ``}, {"metric": {...}, "values": ``: a pure function of
#: the name, so nothing invalidates it; cleared whole at this many names
#: (under 10 MB of 150-byte texts)
METRIC_MEMO_MAX = 65536
_metric_memo: dict = {}
#: the last bodies' buffers, for the next to be joined into (a fresh page
#: costs 9 us on the chip's host, ``native._Spares``)
_bodies: collections.deque = collections.deque(maxlen=2)


def rows(grid_s: np.ndarray, series) -> list:
    """[(metric_name, values text)] of the series that have a point, in
    order; grid_s = the grid in seconds, float64 [T]."""
    if series and native.available():
        buf, row_starts, row_ends, n_points, n_ranges = native.write_matrix(
            grid_s, [ts.values for ts in series])
        POINTS["native"].inc(n_points)
        if n_ranges > 1:
            PARALLEL_POINTS.inc(n_points)
        return [(ts.metric_name, buf[lo:hi])
                for ts, lo, hi in zip(series, row_starts.tolist(),
                                      row_ends.tolist())
                if hi > lo]
    out, n_points = [], 0
    for ts in series:
        vals = [[float(t), fmt_value(v)]
                for t, v in zip(grid_s, ts.values)
                if not math.isnan(v)]
        if vals:
            n_points += len(vals)
            out.append((ts.metric_name, json.dumps(vals).encode()))
    POINTS["python"].inc(n_points)
    return out


def _joined(parts: list) -> memoryview:
    """``b"".join(parts)`` in a kept buffer: a ``BytesIO`` refuses a write
    while a view of its bytes is out, so a body still being sent keeps its
    buffer and the next takes a new one."""
    try:
        bio = _bodies.pop()
        bio.seek(0)
        bio.writelines(parts)
    except (IndexError, BufferError):
        bio = io.BytesIO()
        bio.writelines(parts)
    view = bio.getbuffer()[:bio.tell()]
    _bodies.append(bio)
    return view


def body(head: dict, result: list, trace: dict | None = None) -> memoryview:
    """The response body: ``head`` (status and the partial flags), then
    ``data`` with ``rows``' result, then ``trace`` where there is one.
    Its bytes lie in a kept buffer (``_joined``)."""
    parts = [json.dumps(head).encode()[:-1],
             b', "data": {"resultType": "matrix", "result": [']
    memo, misses = _metric_memo, 0
    for metric_name, values in result:
        key = (metric_name.metric_group, *metric_name.labels)
        row = memo.get(key)
        if row is None:
            misses += 1
            if len(memo) >= METRIC_MEMO_MAX:
                memo.clear()
            row = memo[key] = b'}, {"metric": %b, "values": ' % json.dumps(
                metric_name.to_dict()).encode()
        parts += (row, values)
    MEMO["hit"].inc(len(result) - misses)
    MEMO["miss"].inc(misses)
    if result:
        parts[2] = parts[2][3:]  # the first row follows `[`, not a row
        parts.append(b"}]}")
    else:
        parts.append(b"]}")
    if trace is not None:
        parts += (b', "trace": ', json.dumps(trace).encode())
    parts.append(b"}")
    return _joined(parts)
