"""Differential fuzz: native vm_f2d_grouped must be BIT-IDENTICAL to the
Python float_to_decimal_grouped pipeline (the flush hot path silently
routes through the native twin for batches >= 256 values; any drift
between the two would corrupt stored mantissas undetected).

Both sides share the recurrence-built pow10 table — np.power's SIMD path
differs from libm pow by an ulp at large exponents, which is exactly the
drift this suite guards against."""

import numpy as np
import pytest

from victoriametrics_tpu import native
from victoriametrics_tpu.ops import decimal as dec


def _python_grouped(v, starts):
    """Force the pure-Python pipeline (bypass the native dispatch)."""
    exps = np.zeros(starts.size, dtype=np.int64)
    ends = np.append(starts[1:], v.size)
    sizes = ends - starts
    m, e, normal, specials = dec._f2d_element_phase(v)
    BIG = np.int64(1 << 40)
    absm = np.maximum(np.abs(m).astype(np.float64), 1.0)
    allowed_up = np.floor(
        np.log10(dec.MAX_MANTISSA / absm)).astype(np.int64)
    emin_g = np.minimum.reduceat(np.where(normal, e, BIG), starts)
    floor_g = np.maximum.reduceat(
        np.where(normal, e - allowed_up, -BIG), starts)
    has_norm_g = np.logical_or.reduceat(normal, starts)
    exp_g = np.minimum(emin_g, dec._MAX_EXP)
    exp_g = np.where(floor_g > exp_g, floor_g, exp_g)
    exp_g = np.clip(exp_g, dec._MIN_EXP, dec._MAX_EXP)
    exp_g = np.where(has_norm_g, exp_g, 0)
    exp_elem = np.repeat(exp_g, sizes)
    m_all = dec._f2d_rescale(m, e, normal, exp_elem)
    m_out = dec._f2d_apply_specials(m_all, specials)
    return m_out, exp_g.astype(np.int64)


def _random_starts(rng, n):
    k = max(1, n // 37)
    starts = np.sort(rng.choice(n, size=k, replace=False))
    starts[0] = 0
    return np.unique(starts).astype(np.int64)


def _pow10_neighbours(rng):
    """10^k and the floats an ulp, 1e-10 and 2e-9 either side (where
    floor(log10) turns, and the native code hands the call back to log10),
    and the integers 10^k - 1, 10^k + 1 (where a mantissa's headroom
    turns), of both signs."""
    p = 10.0 ** np.arange(-300, 301)
    near = [p, np.nextafter(p, 0), np.nextafter(p, np.inf)]
    near += [p * (1 + f) for f in (1e-10, -1e-10, 2e-9, -2e-9)]
    ints = 10 ** np.arange(1, 18, dtype=np.int64)
    near += [(ints - 1).astype(np.float64), (ints + 1).astype(np.float64)]
    v = np.concatenate(near)
    return v * np.where(rng.random(v.size) < .5, -1, 1)


CASES = {
    "counters": lambda rng: np.cumsum(
        rng.integers(0, 50, 4000)).astype(np.float64),
    "gauges_3dp": lambda rng: np.round(rng.uniform(-1000, 1000, 4000), 3),
    "full_precision": lambda rng: rng.standard_normal(4000) *
    np.exp(rng.uniform(-200, 200, 4000)),
    "extreme_magnitudes": lambda rng: 10.0 ** rng.uniform(-300, 300, 2000)
    * np.where(rng.random(2000) < .5, -1, 1),
    "large_base_counters": lambda rng: 1e15 + np.cumsum(
        rng.integers(0, 3, 3000)).astype(np.float64),
    "powers_of_ten": _pow10_neighbours,
}


@pytest.mark.skipif(not native.available(), reason="needs native codec")
@pytest.mark.parametrize("case", sorted(CASES))
def test_native_matches_python(case):
    rng = np.random.default_rng(hash(case) % 2 ** 31)
    v = CASES[case](rng)
    starts = _random_starts(rng, v.size)
    m_py, e_py = _python_grouped(v, starts)
    m_c, e_c = native.f2d_grouped(v, starts)
    np.testing.assert_array_equal(m_py, m_c, err_msg=case)
    np.testing.assert_array_equal(e_py, e_c, err_msg=case)


@pytest.mark.skipif(not native.available(), reason="needs native codec")
def test_native_matches_python_specials_and_edges():
    rng = np.random.default_rng(99)
    sp = rng.uniform(0, 100, 1000)
    sp[::7] = np.nan
    sp[1::13] = np.inf
    sp[2::17] = -np.inf
    sp[3::19] = dec.STALE_NAN
    sp[4::23] = 0.0
    edges = np.array([1e-3, 1e3, 0.001, 1000.0, 2 / 3, 1 / 3, 0.1, 0.2,
                      0.3, 123.456, 1e17, -1e17, 9.999999999999999e16,
                      5e-324, 1e-320, 1e-310, 2.2e-308, 1.7e308, -1.7e308])
    for v in (sp, edges):
        starts = _random_starts(rng, v.size)
        m_py, e_py = _python_grouped(v, starts)
        m_c, e_c = native.f2d_grouped(v, starts)
        np.testing.assert_array_equal(m_py, m_c)
        np.testing.assert_array_equal(e_py, e_c)


@pytest.mark.skipif(not native.available(), reason="needs native codec")
def test_grouped_dispatch_uses_native():
    """float_to_decimal_grouped itself (the dispatching entry) must agree
    with the forced-Python path at and above the dispatch threshold."""
    rng = np.random.default_rng(3)
    v = np.round(rng.uniform(-10, 10, 2048), 2)
    starts = _random_starts(rng, v.size)
    m_d, e_d = dec.float_to_decimal_grouped(v, starts)
    m_py, e_py = _python_grouped(v, starts)
    np.testing.assert_array_equal(m_d, m_py)
    np.testing.assert_array_equal(e_d, e_py)
