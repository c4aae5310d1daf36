"""The ``query_range`` matrix answer, from the evaluator's rows to the
response bytes.

``rows`` turns every row's float64 ``[T]`` values into the JSON text of
its ``values`` list in one native pass over the stacked ``[R, T]`` block
(``native.write_matrix``: no Python object a point); ``body`` joins those
texts with each row's ``metric`` object and the envelope, both still from
``json.dumps``.  The bytes equal ``json.dumps`` of the whole answer as a
tree of dicts and lists, default separators included.  Where the native
library is unavailable the Python loop makes the same texts, as every
routine of ``native`` falls back."""

from __future__ import annotations

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


def rows(grid_s: np.ndarray, series) -> list:
    """[(metric_name, values text)] of the series that have a point, in
    order; grid_s = the grid in seconds, float64 [T]."""
    if series and native.available():
        buf, row_ends, n_points = native.write_matrix(
            grid_s, np.stack([ts.values for ts in series]))
        POINTS["native"].inc(n_points)
        out, lo = [], 0
        for ts, hi in zip(series, row_ends.tolist()):
            if hi > lo:
                out.append((ts.metric_name, buf[lo:hi]))
            lo = hi
        return out
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


def body(head: dict, result: list, trace: dict | None = None) -> bytes:
    """The response body: ``head`` (status and the partial flags), then
    ``data`` with ``rows``' result, then ``trace`` where there is one."""
    parts = [json.dumps(head).encode()[:-1],
             b', "data": {"resultType": "matrix", "result": [']
    sep = b'{"metric": '
    for metric_name, values in result:
        parts += (sep, json.dumps(metric_name.to_dict()).encode(),
                  b', "values": ', values)
        sep = b'}, {"metric": '
    parts.append(b"}]}" if result else b"]}")
    if trace is not None:
        parts += (b', "trace": ', json.dumps(trace).encode())
    parts.append(b"}")
    return b"".join(parts)
