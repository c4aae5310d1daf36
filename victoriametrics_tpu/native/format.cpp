// Native writer of a query_range matrix answer's `values` text.
//
// The HTTP front used to make, for every point of the answer, a Python
// float, a formatted str and a two-element list, and then walked them all
// again in json.dumps: 370,000 points a query at 1024 rows x 361 steps,
// four fifths of the served wall. This routine goes from the evaluator's
// [rows, steps] float64 block to the response bytes in one pass. The text
// is byte for byte what json.dumps made of the Python rows:
//   value      query/format_value.fmt_value: NaN is an absent point and is
//              skipped; +Inf / -Inf; an integral value of magnitude under
//              1e15 as an integer (-0.0 -> 0); otherwise repr(float)
//   timestamp  json.dumps(float) = repr(float), formatted once a query
//   layout     [[t, "v"], [t, "v"]]  (json.dumps's default separators)
// repr(float) is the shortest digits that round-trip (std::to_chars gives
// them), laid out by CPython's rule (format_float_short, mode 'r'):
// exponent form where the decimal point lies at or below -4 or above 16,
// a sign and two exponent digits at least, `.0` on an integral mantissa
// in fixed form.
//
// Build: part of libvmcodec.so (see Makefile).

#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <vector>

namespace {

// the longest repr(float): -1.7976931348623157e+308
constexpr int kMaxRepr = 24;
// the most bytes one point can take: `[` t `, "` v `"]` and the `, `
// before the next; the ctypes wrapper sizes the output from the same
// number (MATRIX_POINT_MAX), plus 2 a row for the outer brackets
constexpr int64_t kPointMax = 2 * kMaxRepr + 8;

// repr(v) at p; returns the end.
inline char* py_repr(char* p, double v) {
    char sci[40];
    // [-]d[.ddd]e[+-]XX, shortest round-trip digits
    char* end = std::to_chars(sci, sci + sizeof sci, v,
                              std::chars_format::scientific).ptr;
    if (!std::isfinite(v)) {  // nan, inf, -inf: repr's own spelling
        memcpy(p, sci, end - sci);
        return p + (end - sci);
    }
    const char* s = sci;
    if (*s == '-') *p++ = *s++;
    const char* e = end;
    while (e[-1] != 'e') e--;  // e -> the exponent's sign
    const char* mant_end = e - 1;
    int exp10 = 0;
    for (const char* q = e + 1; q < end; q++) exp10 = exp10 * 10 + (*q - '0');
    if (*e == '-') exp10 = -exp10;
    char digits[20];
    int nd = 0;
    for (const char* q = s; q < mant_end; q++)
        if (*q != '.') digits[nd++] = *q;
    const int decpt = exp10 + 1;  // value = 0.d1d2... x 10^decpt
    if (decpt <= -4 || decpt > 16) {
        *p++ = digits[0];
        if (nd > 1) {
            *p++ = '.';
            memcpy(p, digits + 1, nd - 1);
            p += nd - 1;
        }
        *p++ = 'e';
        *p++ = exp10 < 0 ? '-' : '+';
        const int a = exp10 < 0 ? -exp10 : exp10;
        if (a >= 100) *p++ = '0' + a / 100;
        *p++ = '0' + a / 10 % 10;
        *p++ = '0' + a % 10;
    } else if (decpt <= 0) {
        *p++ = '0';
        *p++ = '.';
        for (int i = decpt; i < 0; i++) *p++ = '0';
        memcpy(p, digits, nd);
        p += nd;
    } else if (decpt >= nd) {
        memcpy(p, digits, nd);
        p += nd;
        for (int i = nd; i < decpt; i++) *p++ = '0';
        *p++ = '.';
        *p++ = '0';
    } else {
        memcpy(p, digits, decpt);
        p += decpt;
        *p++ = '.';
        memcpy(p, digits + decpt, nd - decpt);
        p += nd - decpt;
    }
    return p;
}

// fmt_value(v) of a non-NaN double at p; returns the end.
inline char* fmt_value(char* p, double v) {
    if (std::isinf(v)) {
        memcpy(p, v > 0 ? "+Inf" : "-Inf", 4);
        return p + 4;
    }
    if (std::fabs(v) < 1e15 && v == std::trunc(v))
        return std::to_chars(p, p + kMaxRepr, (int64_t)v).ptr;
    return py_repr(p, v);
}

}  // namespace

extern "C" {

// Writes the `values` text of each row of vals[R][T] (row-contiguous,
// NaN = absent) over the grid[T] (seconds) into out[0..cap):
//   [[t, "v"], [t, "v"], ...]
// one after another with nothing between them. row_ends[i] is the offset
// just past row i's text; a row with no point writes nothing (row_ends[i]
// equals the end before it) and is left out of the answer by the caller.
// *n_points gets the number of points written. Returns the bytes written,
// or -1 if cap is below the stated worst case for R x T.
int64_t vm_write_matrix(const double* grid, int64_t T, const double* vals,
                        int64_t R, uint8_t* out, int64_t cap,
                        int64_t* row_ends, int64_t* n_points) {
    *n_points = 0;
    if (cap < R * (T * kPointMax + 2)) return -1;
    // `[t, "` of every step, once a query
    std::vector<char> pre((size_t)T * (kMaxRepr + 4));
    std::vector<int32_t> pre_off((size_t)T + 1);
    {
        char* p = pre.data();
        for (int64_t j = 0; j < T; j++) {
            pre_off[j] = (int32_t)(p - pre.data());
            *p++ = '[';
            p = py_repr(p, grid[j]);
            memcpy(p, ", \"", 3);
            p += 3;
        }
        pre_off[T] = (int32_t)(p - pre.data());
    }
    char* const base = (char*)out;
    char* p = base;
    int64_t points = 0;
    for (int64_t i = 0; i < R; i++) {
        const double* row = vals + i * T;
        char* const row_start = p;
        for (int64_t j = 0; j < T; j++) {
            const double v = row[j];
            if (v != v) continue;
            if (p == row_start) {
                *p++ = '[';
            } else {
                *p++ = ',';
                *p++ = ' ';
            }
            const int32_t n = pre_off[j + 1] - pre_off[j];
            memcpy(p, pre.data() + pre_off[j], n);
            p = fmt_value(p + n, v);
            *p++ = '"';
            *p++ = ']';
            points++;
        }
        if (p != row_start) *p++ = ']';
        row_ends[i] = p - base;
    }
    *n_points = points;
    return p - base;
}

}  // extern "C"
