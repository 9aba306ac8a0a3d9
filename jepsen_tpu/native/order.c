/* Realtime-order edge sweep for the elle engines, the topological
 * order of a verdict's dependency graph (jt_topo_order, below), the
 * SCC kernel's per-launch edge layout (jt_edge_layout, below), and the
 * list-append prefix pass (jt_off_spine, below).
 *
 * The reference's realtime relation comes from elle's
 * process/realtime graphs (jepsen/src/jepsen/tests/cycle/append.clj
 * wires elle.core's realtime-graph); the Python engine reduces it
 * with a completion-frontier sweep (tpu/elle.py order_edge_arrays).
 * This is that exact sweep in C: events sorted by (position,
 * completion-before-invocation), a covering frontier of completed
 * txns, an edge from every frontier member to each invoking txn.
 * Indices in/out are dense 0..n-1 positions into the caller's txn
 * arrays.
 *
 * Returns the edge count, -1 if cap was too small (caller retries
 * with a bigger buffer), -2 on allocation failure.
 */

#include <stdint.h>
#include <stdlib.h>

#ifdef __cplusplus
extern "C" {
#endif


typedef struct {
    int64_t pos;
    int32_t is_inv;
    int64_t t;
} jt_event;

static int jt_ev_cmp(const void *a, const void *b) {
    const jt_event *x = (const jt_event *)a;
    const jt_event *y = (const jt_event *)b;
    if (x->pos != y->pos)
        return x->pos < y->pos ? -1 : 1;
    if (x->is_inv != y->is_inv)
        return x->is_inv < y->is_inv ? -1 : 1;
    return x->t < y->t ? -1 : (x->t > y->t ? 1 : 0);
}

int64_t jt_realtime_edges(const int64_t *inv, const int64_t *comp,
                          int64_t n, int64_t *out_src,
                          int64_t *out_dst, int64_t cap) {
    if (n <= 0)
        return 0;
    jt_event *events =
        (jt_event *)malloc(sizeof(jt_event) * 2 * (size_t)n);
    int64_t *frontier =
        (int64_t *)malloc(sizeof(int64_t) * (size_t)n);
    if (!events || !frontier) {
        free(events);
        free(frontier);
        return -2;
    }
    for (int64_t i = 0; i < n; i++) {
        events[2 * i].pos = inv[i];
        events[2 * i].is_inv = 1;
        events[2 * i].t = i;
        events[2 * i + 1].pos = comp[i];
        events[2 * i + 1].is_inv = 0;
        events[2 * i + 1].t = i;
    }
    qsort(events, 2 * (size_t)n, sizeof(jt_event), jt_ev_cmp);
    int64_t fn = 0, m = 0;
    for (int64_t e = 0; e < 2 * n; e++) {
        int64_t t = events[e].t;
        if (events[e].is_inv) {
            /* edge from every covering completed txn */
            for (int64_t j = 0; j < fn; j++) {
                int64_t a = frontier[j];
                if (a == t)
                    continue;
                if (m >= cap) {
                    free(events);
                    free(frontier);
                    return -1;
                }
                out_src[m] = a;
                out_dst[m] = t;
                m++;
            }
        } else {
            /* completion: drop frontier members this txn covers
             * (their completion precedes its invocation) */
            int64_t keep = 0;
            for (int64_t j = 0; j < fn; j++)
                if (comp[frontier[j]] >= inv[t])
                    frontier[keep++] = frontier[j];
            fn = keep;
            frontier[fn++] = t;
        }
    }
    free(events);
    free(frontier);
    return m;
}

/* Topological order of a dependency graph: one Kahn pass over a CSR
 * of the edge arrays (the certificate of a valid elle verdict is such
 * an order of the verdict's own graph). Edges are bucketed by source
 * in input order; the queue starts with the sources in id order and
 * is first-in first-out, so the order is a pure function of (n, src,
 * dst) and equals the Python fallback's (tpu/elle.py topo_order).
 * Writes the order into out[0..n) and returns its length: n when the
 * graph is acyclic, less when some node lies on or behind a cycle; -2
 * on allocation failure. */
int64_t jt_topo_order(int64_t n, const int64_t *src, const int64_t *dst,
                      int64_t m, int64_t *out) {
    if (n <= 0)
        return 0;
    int64_t *start = (int64_t *)calloc((size_t)n + 1, sizeof(int64_t));
    int64_t *fill = (int64_t *)malloc(sizeof(int64_t) * (size_t)n);
    int64_t *indeg = (int64_t *)calloc((size_t)n, sizeof(int64_t));
    int64_t *adj = (int64_t *)malloc(sizeof(int64_t) * (size_t)(m + 1));
    if (!start || !fill || !indeg || !adj) {
        free(start);
        free(fill);
        free(indeg);
        free(adj);
        return -2;
    }
    for (int64_t e = 0; e < m; e++) {
        start[src[e] + 1]++;
        indeg[dst[e]]++;
    }
    for (int64_t v = 0; v < n; v++) {
        start[v + 1] += start[v];
        fill[v] = start[v];
    }
    for (int64_t e = 0; e < m; e++)
        adj[fill[src[e]]++] = dst[e];
    int64_t head = 0, tail = 0;
    for (int64_t v = 0; v < n; v++)
        if (indeg[v] == 0)
            out[tail++] = v;
    while (head < tail) {
        int64_t u = out[head++];
        for (int64_t j = start[u]; j < start[u + 1]; j++)
            if (--indeg[adj[j]] == 0)
                out[tail++] = adj[j];
    }
    free(start);
    free(fill);
    free(indeg);
    free(adj);
    return tail;
}

/* The prefix pass of list-append edge inference (tpu/elle_device.py
 * _off_spine_py is the reference): element j of read r, whose rd_len[r]
 * elements follow those of reads 0..r-1 in re_vals, against element j
 * of its key's spine, sp_vals[sp_off[rd_key[r]] + j]. The spine is the
 * key's longest read, so that element exists. Writes the indices of
 * the elements that differ, ascending, into out (room for every
 * element) and returns their count. */
int64_t jt_off_spine(int64_t R, const int64_t *rd_key,
                     const int64_t *rd_len, const int64_t *sp_off,
                     const int64_t *sp_vals, const int64_t *re_vals,
                     int64_t *out) {
    int64_t m = 0, e = 0;
    for (int64_t r = 0; r < R; r++) {
        const int64_t *sp = sp_vals + sp_off[rd_key[r]];
        for (int64_t j = 0, n = rd_len[r]; j < n; j++, e++)
            if (re_vals[e] != sp[j])
                out[m++] = e;
    }
    return m;
}

/* One pass's edge layout for the SCC kernel (tpu/scc.py _layout, whose
 * numpy path is the reference): in each of `blocks` equal blocks of the
 * m edges, a stable counting sort of the block's live edges (on[e] != 0)
 * by receiving node, so each node's edges form one run; the block's
 * masked-off edges follow as self-loops on the sentinel node n_pad-1,
 * each with offset 0. Writes per edge the receiver, sender and offset in
 * its run, and per block and node the run's last position in the block
 * or -1. Returns 0; -1 if a live receiver lies outside [0, n_pad-1); -2
 * on allocation failure. */
int64_t jt_edge_layout(int64_t n_pad, int64_t blocks, int64_t m,
                       const int32_t *recv, const int32_t *send,
                       const uint8_t *on, int32_t *out_recv,
                       int32_t *out_send, int32_t *out_off,
                       int32_t *out_end) {
    int64_t step = m / blocks;
    /* int32 positions: the edge arrays are int32-indexed on the device */
    int32_t *fill = (int32_t *)malloc(sizeof(int32_t) * (size_t)n_pad);
    if (!fill)
        return -2;
    for (int64_t b = 0; b < blocks; b++) {
        const int32_t *r = recv + b * step, *s = send + b * step;
        const uint8_t *o = on + b * step;
        int32_t *orecv = out_recv + b * step, *osend = out_send + b * step;
        int32_t *ooff = out_off + b * step, *oend = out_end + b * n_pad;
        for (int64_t v = 0; v < n_pad; v++)
            fill[v] = 0;
        for (int64_t e = 0; e < step; e++) {
            if (!o[e])
                continue;
            if (r[e] < 0 || r[e] >= n_pad - 1) {
                free(fill);
                return -1;
            }
            fill[r[e]]++;
        }
        int32_t pos = 0;
        for (int64_t v = 0; v < n_pad; v++) {
            int32_t c = fill[v];
            fill[v] = pos;
            oend[v] = c ? pos + c - 1 : -1;
            pos += c;
        }
        int32_t live = pos;
        for (int64_t e = 0; e < step; e++) {
            int32_t p;
            if (o[e]) {
                p = fill[r[e]]++;
                orecv[p] = r[e];
                osend[p] = s[e];
            } else {
                p = pos++;
                orecv[p] = osend[p] = (int32_t)(n_pad - 1);
            }
        }
        /* offsets in one sequential pass: a run starts where the
         * receiver changes; masked-off edges each start their own */
        for (int64_t p = 0; p < step; p++)
            ooff[p] = (p > 0 && p < live && orecv[p] == orecv[p - 1])
                      ? ooff[p - 1] + 1 : 0;
    }
    free(fill);
    return 0;
}

#ifdef __cplusplus
}  /* extern "C" */
#endif
