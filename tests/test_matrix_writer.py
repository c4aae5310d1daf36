"""The native matrix writer (native/format.cpp, httpapi/matrix.py) writes
byte for byte what one ``json.dumps`` over a tree of Python rows wrote:
edge values of ``fmt_value`` and ``repr(float)``, absent points, random
bit patterns, sub-second grids - and so does the Python fallback that
serves where the library is missing."""

import numpy as np
import pytest

from tests.apptest_helpers import tree_matrix_body
from victoriametrics_tpu import native
from victoriametrics_tpu.httpapi import matrix
from victoriametrics_tpu.query.types import Timeseries
from victoriametrics_tpu.storage.metric_name import MetricName

NAN = float("nan")
T0 = 1_727_776_800.0
HEAD = {"status": "success", "isPartial": False, "partialResolution": True}

EDGE_VALUES = [
    0.0, -0.0, 1e15, 1e16, 1e15 - 1, -(1e15 - 1), 2.0 ** 53, 1e14 + 0.5,
    1e-4, 1e-5, 1.5e-7, 0.1, 1e22, 5e-324, 1.7976931348623157e308,
    -1.7976931348623157e308, 123456789012345678.0, -3.5, 9999999999999998.0,
    1.2345678901234567e16, 2.2250738585072014e-308, 0.30000000000000004,
    float("inf"), float("-inf"),
    float(np.float32(437.2184753)), float(np.float32(0.1)),
    float(np.float32(16777217.0)), float(np.float32(3.4028235e38)),
]


def _grid(t, step=60.0, start=T0):
    return start + step * np.arange(t, dtype=np.float64)


def _random_bits(seed, r=48, t=257):
    """Random 64-bit patterns as doubles: every exponent, subnormals, the
    odd Inf, and NaN (1 in 2048) as the odd absent point."""
    bits = np.random.default_rng(seed).integers(
        0, 2 ** 64, size=(r, t), dtype=np.uint64)
    return bits.view(np.float64)


def _f32_block(seed, r=32, t=361):
    """What the device path answers: float32 rates widened to float64."""
    rng = np.random.default_rng(seed)
    return (rng.random((r, t)) * 10.0 ** rng.integers(-6, 9, size=(r, 1))
            ).astype(np.float32).astype(np.float64)


CASES = [(f"edge:{v!r}", _grid(3), [[v, NAN, -v]]) for v in EDGE_VALUES] + [
    ("nan:leading", _grid(5), [[NAN, NAN, 1.5, 2.0, 3.25]]),
    ("nan:trailing", _grid(5), [[1.5, 2.0, 3.25, NAN, NAN]]),
    ("nan:interior", _grid(5), [[1.5, NAN, NAN, 2.0, 3.25]]),
    ("nan:alternating", _grid(6), [[NAN, 1.0, NAN, 2.5, NAN, 4.0]]),
    ("nan:one_row_of_three", _grid(4), [[1.0, 2.0, NAN, 4.0], [NAN] * 4,
                                        [NAN, NAN, NAN, 0.5]]),
    ("nan:first_and_last_rows", _grid(2), [[NAN, NAN], [1.0, 2.0],
                                           [NAN, NAN]]),
    ("nan:every_row", _grid(3), [[NAN] * 3, [NAN] * 3]),
    ("shape:no_rows", _grid(4), np.empty((0, 4))),
    ("shape:one_step", _grid(1), [[7.0], [NAN], [0.25]]),
    ("shape:one_point", _grid(1), [[42.0]]),
    ("grid:500ms", _grid(9, 0.5), _f32_block(1, 3, 9)),
    ("grid:250ms", _grid(8, 0.25), _f32_block(2, 2, 8)),
    ("grid:ms", _grid(7, 0.001, T0 + 0.123), _f32_block(3, 2, 7)),
    ("grid:from_zero", _grid(4, 15.0, 0.0), [[1.0, 2.0, 3.0, 4.5]]),
    ("grid:negative", _grid(4, 0.5, -1.0), [[1.0, 2.0, 3.0, 4.5]]),
    ("grid:exponent_form", _grid(3, 4e15, 8e15), [[1.0, 2.0, 3.0]]),
    ("block:f32_dashboard", _grid(361), _f32_block(4)),
    ("block:integers", _grid(50), np.random.default_rng(5).integers(
        -10 ** 15, 10 ** 15, size=(8, 50)).astype(np.float64)),
] + [(f"bits:seed{s}", _grid(257), _random_bits(s)) for s in (11, 12, 13, 14)]


def _series(block):
    block = np.asarray(block, dtype=np.float64)
    return [Timeseries(MetricName(b"m", [(b"row", str(i).encode()),
                                          (b"quote", b'a"b\\c\n\xc3\xa9')]),
                       row)
            for i, row in enumerate(block)]


@pytest.mark.requires_native
@pytest.mark.parametrize("grid,block", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_writer_equals_the_tree_dump(grid, block, monkeypatch):
    series = _series(block)
    points = int(np.count_nonzero(~np.isnan(np.asarray(block, np.float64))))
    want = tree_matrix_body(grid, series, HEAD)
    trace = {"duration_msec": 0.25, "message": "q", "children": []}

    before = {w: c.get() for w, c in matrix.POINTS.items()}
    result = matrix.rows(grid, series)
    assert matrix.body(HEAD, result) == want
    assert (matrix.body(HEAD, result, trace)
            == tree_matrix_body(grid, series, HEAD, trace))
    assert matrix.POINTS["native"].get() - before["native"] == points
    assert matrix.POINTS["python"].get() == before["python"]

    # the fallback, and what the wrapper promises of its buffer
    monkeypatch.setattr(native, "available", lambda: False)
    assert matrix.body(HEAD, matrix.rows(grid, series)) == want
    assert matrix.POINTS["python"].get() - before["python"] == points
    buf, row_ends, n = native.write_matrix(
        grid, np.asarray(block, np.float64).reshape(-1, grid.size))
    assert n == points and len(buf) == (row_ends[-1] if len(row_ends) else 0)
    assert len(buf) <= len(series) * (grid.size * native.MATRIX_POINT_MAX + 2)
