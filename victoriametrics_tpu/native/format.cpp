// Native writer of a query_range matrix answer's `values` text.
//
// The HTTP front used to make, for every point of the answer, a Python
// float, a formatted str and a two-element list, and then walked them all
// again in json.dumps: 370,000 points a query at 1024 rows x 361 steps,
// four fifths of the served wall. This routine goes from the evaluator's
// [rows, steps] float64 block to the response bytes in one pass, which a
// large answer's rows share out among threads (vm_write_matrix, below).
// The text is byte for byte what json.dumps made of the Python rows:
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

#include <algorithm>
#include <atomic>
#include <charconv>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <functional>
#include <mutex>
#include <system_error>
#include <thread>
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

// The `values` text of rows [lo, hi) of vals[R][T], written from `p` on:
// one row after another with nothing between them, each row's first and
// last byte offset (from `base`) into row_starts / row_ends. Returns the
// points written. Rows are independent of each other: a row's text
// depends on the row and the grid's prefixes only, so any cut of the
// rows into ranges writes the same bytes a row.
int64_t write_rows(const double* vals, int64_t T, int64_t lo, int64_t hi,
                   const char* pre, const int32_t* pre_off,
                   const char* base, char* p, int64_t* row_starts,
                   int64_t* row_ends) {
    int64_t points = 0;
    for (int64_t i = lo; i < hi; i++) {
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
            memcpy(p, pre + pre_off[j], n);
            p = fmt_value(p + n, v);
            *p++ = '"';
            *p++ = ']';
            points++;
        }
        if (p != row_start) *p++ = ']';
        row_starts[i] = row_start - base;
        row_ends[i] = p - base;
    }
    return points;
}

// One range of rows for every so many points of the block: under two
// ranges' worth (a millisecond or two of formatting, a point being 65-95
// ns of std::to_chars) the call stays on its own thread. Read on the
// chip's host, PERF.md section 6, PR 33.
constexpr int64_t kPointsPerRange = 8192;
// the most ranges a call cuts: 12 and 16 read no better than 8 at any
// size on the chip's host (13 cores, shared with the fetch pool, the
// flusher and the client)
constexpr int64_t kMaxRanges = 8;

// The ranges of one call, claimed one at a time by whoever gets there:
// the calling thread always, and as many helpers as took a ticket.
struct Job {
    const std::function<void(int64_t)>& write_range;
    const int64_t n;
    std::atomic<int64_t> next{0};
    int64_t inside = 0;  // helpers in claim(); guarded by Helpers::mu
    void claim() {
        for (int64_t k; (k = next.fetch_add(1)) < n;) write_range(k);
    }
};

// The helper threads, made on the first answer large enough to be cut and
// kept: making and joining a thread costs 140-200 us on the chip's host
// (PERF.md section 6, PR 33), a range's whole work at 2500 points. A call
// waits only for helpers that have taken a ticket of its job, and writes
// itself every range no helper claimed: where no thread can be had, or
// none is left (a forked child), it is the single pass, never a wait.
struct Helpers {
    std::mutex mu;
    std::condition_variable work, left;
    std::vector<Job*> tickets;
    int64_t threads = 0;

    void loop() {
        std::unique_lock<std::mutex> lock(mu);
        for (;;) {
            work.wait(lock, [&] { return !tickets.empty(); });
            Job* job = tickets.back();
            tickets.pop_back();
            job->inside++;
            lock.unlock();
            job->claim();
            lock.lock();
            if (--job->inside == 0) left.notify_all();
        }
    }

    void run(Job& job) {
        {
            std::lock_guard<std::mutex> lock(mu);
            while (threads < std::min(job.n, kMaxRanges) - 1) {
                try {
                    std::thread(&Helpers::loop, this).detach();
                } catch (const std::system_error&) {
                    break;
                }
                threads++;
            }
            tickets.insert(tickets.end(), std::min(threads, job.n - 1),
                           &job);
        }
        work.notify_all();
        job.claim();
        std::unique_lock<std::mutex> lock(mu);
        tickets.erase(std::remove(tickets.begin(), tickets.end(), &job),
                      tickets.end());
        left.wait(lock, [&] { return job.inside == 0; });
    }
};

// never destroyed: its threads outlive main's statics
Helpers& helpers() {
    static Helpers* const h = new Helpers;
    return *h;
}

}  // namespace

extern "C" {

// Writes the `values` text of each row of vals[R][T] (row-contiguous,
// NaN = absent) over the grid[T] (seconds) into out[0..cap):
//   [[t, "v"], [t, "v"], ...]
// The rows are cut into `ranges` contiguous ranges (held to 1..R), written
// at once by the caller and the helper threads, each into its own region
// of `out`: a range begins at the worst-case offset of its first
// row, row * (T * kPointMax + 2), so no range waits for another's length
// and the one-range call is the single contiguous pass. Row i's text is
// out[row_starts[i]..row_ends[i]), empty for a row with no point, which
// the caller leaves out of the answer; a row's bytes do not depend on the
// cut. *n_points gets the number of points written. Returns the offset
// just past the last row's text, or -1 if cap is below the stated worst
// case for R x T.
int64_t vm_write_matrix_cut(const double* grid, int64_t T,
                            const double* vals, int64_t R, uint8_t* out,
                            int64_t cap, int64_t* row_starts,
                            int64_t* row_ends, int64_t* n_points,
                            int64_t ranges) {
    *n_points = 0;
    const int64_t row_max = T * kPointMax + 2;
    if (cap < R * row_max) return -1;
    if (R == 0) return 0;
    // `[t, "` of every step, once a query, read by every range
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
    const int64_t n = ranges < 1 ? 1 : ranges > R ? R : ranges;
    std::vector<int64_t> points((size_t)n, 0);
    const std::function<void(int64_t)> write_range = [&](int64_t k) {
        const int64_t lo = R * k / n, hi = R * (k + 1) / n;
        points[k] = write_rows(vals, T, lo, hi, pre.data(), pre_off.data(),
                               base, base + lo * row_max, row_starts,
                               row_ends);
    };
    if (n == 1) {
        write_range(0);
    } else {
        Job job{write_range, n};
        helpers().run(job);
    }
    for (int64_t c : points) *n_points += c;
    return row_ends[R - 1];
}

// vm_write_matrix_cut at the width the call observes: one range for every
// kPointsPerRange points of R x T, up to kMaxRanges and the machine's
// cores; *n_ranges gets the number cut (1: written inline, no thread
// made or woken).
int64_t vm_write_matrix(const double* grid, int64_t T, const double* vals,
                        int64_t R, uint8_t* out, int64_t cap,
                        int64_t* row_starts, int64_t* row_ends,
                        int64_t* n_points, int64_t* n_ranges) {
    // read once: glibc opens /sys/devices/system/cpu/online at every call
    static const int64_t cores = std::thread::hardware_concurrency();
    const int64_t n = std::max<int64_t>(
        1, std::min({R * T / kPointsPerRange, kMaxRanges, cores, R}));
    *n_ranges = n;
    return vm_write_matrix_cut(grid, T, vals, R, out, cap, row_starts,
                               row_ends, n_points, n);
}

}  // extern "C"
