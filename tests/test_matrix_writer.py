"""The native matrix writer (native/format.cpp, httpapi/matrix.py) writes
byte for byte what one ``json.dumps`` over a tree of Python rows wrote:
edge values of ``fmt_value`` and ``repr(float)``, absent points, random
bit patterns, sub-second grids - and so does the Python fallback that
serves where the library is missing.  So does any cut of the rows into
ranges written at once, a ``metric`` object taken from ``body``'s memo,
and an answer written into the buffers an earlier one has left."""

import gzip
import json
import os
import sys
import threading

import numpy as np
import pytest

from tests.apptest_helpers import tree_matrix_body
from victoriametrics_tpu import native
from victoriametrics_tpu.httpapi import matrix
from victoriametrics_tpu.query.format_value import fmt_value
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
    buf, _, row_ends, n, ranges = native.write_matrix(
        grid, np.asarray(block, np.float64).reshape(-1, grid.size))
    assert n == points and len(buf) == (row_ends[-1] if len(row_ends) else 0)
    assert len(buf) <= len(series) * (grid.size * native.MATRIX_POINT_MAX + 2)
    assert ranges == 1  # every case here is a small answer: no thread made


# -- the rows cut into ranges --------------------------------------------------

def _texts(written):
    buf, row_starts, row_ends = written[:3]
    return [bytes(buf[lo:hi]) for lo, hi in zip(row_starts, row_ends)]


def _edge_block(r=24, t=37):
    """All-NaN rows wherever a 2-, 3- or 8-range cut of r rows has an
    edge (and at both ends), +-Inf, integral values, 1e15 and 1e-5."""
    block = _f32_block(21, r, t)
    edges = {r * k // n for n in (2, 3, 8) for k in range(n + 1)}
    for i in edges | {e - 1 for e in edges}:
        if 0 <= i < r:
            block[i] = NAN
    block[1, :6] = [np.inf, -np.inf, 1e15, 1e-5, -1e-5, 12345.0]
    block[r - 2, -6:] = [1e15 - 1, 1.5e-5, -np.inf, np.inf, 0.0, -0.0]
    block[5, ::2] = NAN
    return block


CUT_BLOCKS = [
    ("edges", _grid(37), _edge_block()),
    ("random_bits", _grid(257), _random_bits(15, 19, 257)),
    ("one_row", _grid(50), _f32_block(22, 1, 50)),
    ("one_step", _grid(1), [[7.0], [NAN], [0.25], [NAN], [1e15], [1e-5],
                            [np.inf]]),
    ("one_point", _grid(1), [[42.0]]),
    ("every_row_empty", _grid(3), [[NAN] * 3] * 5),
]
# 1000: more ranges than any block here has rows
CUTS = [1, 2, 3, 8, 1000]


@pytest.mark.requires_native
@pytest.mark.parametrize("ranges", CUTS)
@pytest.mark.parametrize("grid,block", [c[1:] for c in CUT_BLOCKS],
                         ids=[c[0] for c in CUT_BLOCKS])
def test_any_cut_writes_the_single_pass_bytes(grid, block, ranges):
    block = np.asarray(block, np.float64)
    single = native.write_matrix_cut(grid, block, 1)
    cut = native.write_matrix_cut(grid, block, ranges)
    assert cut[4] == min(ranges, len(block))
    assert _texts(cut) == _texts(single)
    assert cut[3] == single[3] == int(np.count_nonzero(~np.isnan(block)))
    # a row with a point is json.dumps of its Python rows; one without
    # is empty
    want = [json.dumps([[float(t), fmt_value(v)]
                        for t, v in zip(grid, row) if v == v]).encode()
            for row in block]
    assert _texts(cut) == [w if w != b"[]" else b"" for w in want]
    # the single pass is one contiguous text, as it always was
    assert b"".join(_texts(single)) == bytes(single[0])


def _answer(points_at_least, t=361, seed=31):
    r = -(-points_at_least // t)
    return _grid(t), _series(_f32_block(seed, r, t))


@pytest.mark.requires_native
def test_the_width_follows_the_answers_size():
    """Under two ranges' worth of points the call makes no thread and the
    parallel counter stays; above it the answer's points are added, once,
    and the body is still the tree's dump."""
    per_range = 8192  # format.cpp kPointsPerRange
    grid, small = _answer(per_range)  # 23 rows x 361: 8303 points
    before = matrix.PARALLEL_POINTS.get()
    assert native.write_matrix(grid, [ts.values for ts in small])[4] == 1
    assert matrix.body(HEAD, matrix.rows(grid, small)) == tree_matrix_body(
        grid, small, HEAD)
    assert matrix.PARALLEL_POINTS.get() == before

    grid, large = _answer(8 * per_range)
    block = np.stack([ts.values for ts in large])
    ranges = native.write_matrix(grid, block)[4]
    assert ranges == min(8, os.cpu_count())  # format.cpp kMaxRanges
    if ranges == 1:
        pytest.skip("one core: the call cuts nothing here")
    native_before = matrix.POINTS["native"].get()
    assert matrix.body(HEAD, matrix.rows(grid, large)) == tree_matrix_body(
        grid, large, HEAD)
    assert matrix.PARALLEL_POINTS.get() - before == block.size
    assert matrix.POINTS["native"].get() - native_before == block.size


@pytest.mark.requires_native
def test_two_answers_written_at_once_get_their_own_bytes():
    """Two serving threads in the native call together, each cutting its
    own rows into ranges that the same helper threads write, and each
    answer in buffers of its own (an answer still held keeps its)."""
    answers = [_answer(3 * 8192, seed=s) for s in (41, 42)]
    want = [tree_matrix_body(g, srs, HEAD) for g, srs in answers]
    got, errors = [[], []], []

    def serve(i):
        try:
            grid, series = answers[i]
            for _ in range(8):
                got[i].append(matrix.body(HEAD, matrix.rows(grid, series)))
        except Exception as e:  # surfaced below, on the test's thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=serve, args=(i,))
                   for i in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(th.is_alive() for th in threads)
    for i in range(2):
        assert got[i] == [want[i]] * 8


# -- the metric objects' memo ---------------------------------------------------

MEMO_NAMES = [
    MetricName(b"http_requests_total", [(b"instance", b"h-1"),
                                        (b"job", b"api")]),
    MetricName(b"", [(b"instance", b"h-1")]),
    MetricName(b"", []),
    MetricName(b"m", [(b"quote", b'a"b\\c\n'),
                      (b"utf8", "caf\u00e9 \u4e16".encode())]),
    MetricName(b"m", [(b"b", b"2"), (b"a", b"1")]),  # unsorted: kept so
    MetricName(b"m", [(b"a", b"1"), (b"b", b"2")]),
    MetricName(b"ma", [(b"", b"x")]),
]


def _named(names, t=3):
    grid = _grid(t)
    return grid, [Timeseries(mn, np.full(t, float(i)))
                  for i, mn in enumerate(names)]


def test_body_with_the_memo_equals_the_tree_dump():
    """A first call (every name a miss) and a second (every name a hit)
    both equal json.dumps of the whole tree, for names with and without
    __name__, quotes and non-ASCII in a value, and label orders that
    differ."""
    matrix._metric_memo.clear()
    grid, series = _named(MEMO_NAMES)
    want = tree_matrix_body(grid, series, HEAD)
    before = {r: c.get() for r, c in matrix.MEMO.items()}
    assert matrix.body(HEAD, matrix.rows(grid, series)) == want
    assert matrix.MEMO["miss"].get() - before["miss"] == len(MEMO_NAMES)
    assert matrix.MEMO["hit"].get() == before["hit"]
    assert matrix.body(HEAD, matrix.rows(grid, series)) == want
    assert matrix.MEMO["miss"].get() - before["miss"] == len(MEMO_NAMES)
    assert matrix.MEMO["hit"].get() - before["hit"] == len(MEMO_NAMES)
    assert len(matrix._metric_memo) == len(MEMO_NAMES)


def test_the_memo_stays_under_its_bound(monkeypatch):
    """Full, it is cleared whole and goes on answering: more names than
    the bound in one answer, then the same answer again."""
    monkeypatch.setattr(matrix, "METRIC_MEMO_MAX", 8)
    matrix._metric_memo.clear()
    names = [MetricName(b"m", [(b"i", str(i).encode())]) for i in range(21)]
    grid, series = _named(names)
    want = tree_matrix_body(grid, series, HEAD)
    for _ in range(2):
        assert matrix.body(HEAD, matrix.rows(grid, series)) == want
        assert 0 < len(matrix._metric_memo) <= 8


# -- the spare buffers ---------------------------------------------------------

@pytest.mark.requires_native
def test_an_answer_keeps_its_bytes_while_the_next_is_written():
    """The text buffer of an answer is written into again only once no
    view of that answer is left: two answers held together read their
    own bytes, and a dropped answer's buffer serves the next."""
    grid = _grid(9)
    first = native.write_matrix(grid, np.full((3, 9), 1.5))
    kept = _texts(first)
    second = native.write_matrix(grid, np.full((3, 9), 2.5))
    assert _texts(first) == kept != _texts(second)
    assert first[0].obj is not second[0].obj
    spare = id(second[0].obj)  # no reference: that would be a holder
    del first, second
    third = native.write_matrix(grid, np.full((2, 9), 3.5))
    assert id(third[0].obj) == spare
    assert _texts(third) == [json.dumps(
        [[float(t), "3.5"] for t in grid]).encode()] * 2


def test_a_body_keeps_its_bytes_while_the_next_is_joined():
    """A body still held (being sent) is not written over: the next is
    joined into a buffer of its own; a dropped body's buffer serves the
    next, also a shorter one."""
    matrix._bodies.clear()
    grid, series = _named(MEMO_NAMES[:3], t=40)
    result = matrix.rows(grid, series)
    want = tree_matrix_body(grid, series, HEAD)
    first = matrix.body(HEAD, result)
    other = matrix.body({"status": "error"}, result[:1])
    assert first == want
    assert other == tree_matrix_body(grid, series[:1], {"status": "error"})
    assert first == want  # still, after the next was joined
    kept = list(matrix._bodies)
    del first, other
    short = matrix.body(HEAD, result[:1])
    assert len(kept) == 1 and list(matrix._bodies) == kept
    assert short == tree_matrix_body(grid, series[:1], HEAD)
    assert gzip.decompress(gzip.compress(short, 1)) == short
