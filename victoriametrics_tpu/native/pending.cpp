// Native ordering of freshly ingested columnar rows: the pending-chunk ->
// in-memory-part conversion's sort and gathers in one pass.
//
// storage/partition.py used to order a batch with np.lexsort over seven
// key columns (acc, proj, grp, job, inst, mid, ts), each gathered from the
// id space per row, and then reorder five row columns by the result: a
// dozen NumPy passes, 85 % of the conversion, on the serving thread of
// every query that meets fresh rows. Every row of a batch carries a dense
// id into ONE id space whose six TSID key columns are fixed once the id is
// registered, so the TSID order of the ids belongs to the space, not to
// the batch: the caller hands a per-space `rank` (equal keys share one),
// and the order lexsort gives is
//   a counting sort of the rows by rank[id]       (stable: ingest order)
//   a stable sort by ts inside each rank's run    (short, mostly sorted)
// with ts, value, id and metric id written at the row's final place as it
// is found. Ties on (series, ts) keep ingest order, as lexsort leaves
// them: dedup's keep-last depends on it.
//
// Build: part of libvmcodec.so (see Makefile).

#include <algorithm>
#include <cstdint>
#include <new>
#include <vector>

namespace {

// runs up to this long sort by insertion (a scrape batch holds a handful
// of rows a series); longer ones through an index sort
constexpr int64_t kInsertionMax = 48;
// past this many ranks a row the counting sort's table costs more than a
// comparison sort of the rows
constexpr int64_t kSparseRanks = 8;

// The three row columns reordered by idx (row i takes row idx[i]), over
// rows [a, a + idx.size()).
void apply_order(int64_t* ts, double* vals, int64_t* loc, int64_t a,
                 const std::vector<int64_t>& idx, std::vector<int64_t>& tmp_i,
                 std::vector<double>& tmp_d) {
    const int64_t len = (int64_t)idx.size();
    tmp_i.resize(len);
    tmp_d.resize(len);
    for (int64_t i = 0; i < len; i++) tmp_i[i] = ts[idx[i]];
    std::copy(tmp_i.begin(), tmp_i.end(), ts + a);
    for (int64_t i = 0; i < len; i++) tmp_i[i] = loc[idx[i]];
    std::copy(tmp_i.begin(), tmp_i.end(), loc + a);
    for (int64_t i = 0; i < len; i++) tmp_d[i] = vals[idx[i]];
    std::copy(tmp_d.begin(), tmp_d.end(), vals + a);
}

// Stable sort of rows [a, b) of the three row columns by ts.
void sort_run(int64_t* ts, double* vals, int64_t* loc, int64_t a, int64_t b,
              std::vector<int64_t>& idx, std::vector<int64_t>& tmp_i,
              std::vector<double>& tmp_d) {
    if (b - a <= kInsertionMax) {
        for (int64_t i = a + 1; i < b; i++) {
            const int64_t t = ts[i];
            if (ts[i - 1] <= t) continue;
            const double v = vals[i];
            const int64_t l = loc[i];
            int64_t j = i;
            while (j > a && ts[j - 1] > t) {
                ts[j] = ts[j - 1];
                vals[j] = vals[j - 1];
                loc[j] = loc[j - 1];
                j--;
            }
            ts[j] = t;
            vals[j] = v;
            loc[j] = l;
        }
        return;
    }
    idx.resize(b - a);
    for (int64_t i = a; i < b; i++) idx[i - a] = i;
    std::stable_sort(idx.begin(), idx.end(),
                     [ts](int64_t x, int64_t y) { return ts[x] < ts[y]; });
    apply_order(ts, vals, loc, a, idx, tmp_i, tmp_d);
}

}  // namespace

extern "C" {

// The batch is `n_chunks` chunks in ingest order; chunk c holds lens[c]
// rows of (ids, ts, vals). rank[n_ids] maps a dense id to its place in the
// space's TSID order, 0 <= rank < n_ranks; mid[n_ids] is the space's
// metric-id column. Writes the n rows in (rank, ts) order, stable, to
// out_ts / out_vals / out_loc (the row's id) / out_mid, and the start row
// of every block to out_starts (room for n): a block is a run of one
// metric id, cut every max_block_rows. Returns the number of blocks, -1
// for an id outside the rank, -2 when memory runs out.
int64_t vm_pending_order(const int64_t* const* ids_p,
                         const int64_t* const* ts_p,
                         const double* const* vals_p, const int64_t* lens,
                         int64_t n_chunks, const int32_t* rank,
                         int64_t n_ids, int64_t n_ranks, const uint64_t* mid,
                         int64_t max_block_rows, int64_t* out_ts,
                         double* out_vals, int64_t* out_loc,
                         uint64_t* out_mid, int64_t* out_starts) {
    try {
        int64_t n = 0;
        for (int64_t c = 0; c < n_chunks; c++) {
            const int64_t* ids = ids_p[c];
            for (int64_t i = 0; i < lens[c]; i++)
                if (ids[i] < 0 || ids[i] >= n_ids) return -1;
            n += lens[c];
        }
        std::vector<int64_t> idx, tmp_i;
        std::vector<double> tmp_d;
        if (n_ranks > kSparseRanks * n) {
            // a small batch of a large space: a table of every rank would
            // cost more than the rows; one stable sort by (rank, ts)
            int64_t p = 0;
            for (int64_t c = 0; c < n_chunks; c++)
                for (int64_t i = 0; i < lens[c]; i++, p++) {
                    out_ts[p] = ts_p[c][i];
                    out_vals[p] = vals_p[c][i];
                    out_loc[p] = ids_p[c][i];
                }
            idx.resize(n);
            for (int64_t i = 0; i < n; i++) idx[i] = i;
            std::stable_sort(idx.begin(), idx.end(),
                             [=](int64_t x, int64_t y) {
                const int32_t rx = rank[out_loc[x]], ry = rank[out_loc[y]];
                return rx != ry ? rx < ry : out_ts[x] < out_ts[y];
            });
            apply_order(out_ts, out_vals, out_loc, 0, idx, tmp_i, tmp_d);
        } else {
            // pass 1: rows a rank
            std::vector<int64_t> off(n_ranks + 1, 0);
            for (int64_t c = 0; c < n_chunks; c++)
                for (int64_t i = 0; i < lens[c]; i++)
                    off[rank[ids_p[c][i]] + 1]++;
            for (int64_t r = 0; r < n_ranks; r++) off[r + 1] += off[r];
            // pass 2: every row to its rank's run, in ingest order
            std::vector<int64_t> at(off.begin(), off.end() - 1);
            for (int64_t c = 0; c < n_chunks; c++) {
                const int64_t* ids = ids_p[c];
                const int64_t* ts = ts_p[c];
                const double* vals = vals_p[c];
                for (int64_t i = 0; i < lens[c]; i++) {
                    const int64_t p = at[rank[ids[i]]]++;
                    out_ts[p] = ts[i];
                    out_vals[p] = vals[i];
                    out_loc[p] = ids[i];
                }
            }
            // pass 3: ts order inside each run that is not in it yet
            for (int64_t r = 0; r < n_ranks; r++) {
                const int64_t a = off[r], b = off[r + 1];
                for (int64_t i = a + 1; i < b; i++)
                    if (out_ts[i - 1] > out_ts[i]) {
                        sort_run(out_ts, out_vals, out_loc, a, b, idx, tmp_i,
                                 tmp_d);
                        break;
                    }
            }
        }
        // the metric ids, and the blocks
        int64_t k = 0, block_start = 0;
        for (int64_t i = 0; i < n; i++) {
            const uint64_t m = mid[out_loc[i]];
            out_mid[i] = m;
            if (i == 0 || m != out_mid[i - 1] ||
                i - block_start == max_block_rows) {
                block_start = i;
                out_starts[k++] = i;
            }
        }
        return k;
    } catch (const std::bad_alloc&) {
        return -2;
    }
}

}  // extern "C"
