"""Device-path list-append analysis: interned int arrays + vectorized
edge inference + batched SCC.

Capability reference: elle 0.2.1 behind
jepsen/src/jepsen/tests/cycle/append.clj:6-27 — infer ww/wr/rw
dependency edges from txn external reads/writes, search for cycles,
classify anomalies. The host engine (jepsen_tpu.tpu.elle) is the
correctness reference; this module re-derives the same anomalies with:

  1. one flattening pass turning txn micro-ops into dense int arrays
     (txn ids, interned keys, (key, value) pair ids);
  2. numpy segment ops for version orders (spines), writer
     resolution, read anomalies (G1a/G1b/internal/unobservable/
     incompatible), and ww/wr/rw edge inference — no per-element
     Python. Read elements resolve by spine position: one elementwise
     pass checks that each read is a prefix of its key's spine, and an
     element on its spine takes the spine element's pair id, so only
     appends, spines and the elements off them are interned;
  3. cycle detection through the batched label-propagation SCC kernel
     (jepsen_tpu.tpu.scc) on device, host scipy on fallback;
  4. host-side cycle witness extraction and classification (shared
     with the host engine).

Histories whose append values aren't machine ints (or whose key/value
ranges overflow the pair packing) raise Unvectorizable and the caller
drops to the host engine, so the fast path never changes results.
"""

from __future__ import annotations

from collections import defaultdict

import numpy as np

from .. import history as h
from .. import native, telemetry
from ..history import History
from . import profiler
from . import scc as scc_mod
from .elle import (EDGE_NAMES, PROC, RT, RW, WR, WW, Txn, _classify,
                   _find_cycle, collect, order_edges_from_arrays,
                   realtime_edges_arrays, topo_order)

_TYPE_OK, _TYPE_INFO, _TYPE_FAIL = 0, 1, 2
_T_CODE = {h.OK: _TYPE_OK, h.INFO: _TYPE_INFO, h.FAIL: _TYPE_FAIL}

_KEY_BITS = 23
_VAL_BITS = 40


class Unvectorizable(Exception):
    """History can't take the int-array fast path."""


def _dense_first_seen(xs: np.ndarray) -> np.ndarray:
    """Raw ids -> dense codes in FIRST-SEEN order, matching the
    Python flattener's process interning dict."""
    if not len(xs):
        return xs
    _u, first, inv = np.unique(xs, return_index=True,
                               return_inverse=True)
    order = np.argsort(first, kind="stable")
    rank = np.empty(len(order), dtype=np.int64)
    rank[order] = np.arange(len(order))
    return rank[inv]


def _txn_mops(ops: list, arrs: dict, ti: int):
    """A txn's effective micro-ops, mirroring collect(): the completion
    value for committed txns (unless None), else the invocation's."""
    op = ops[int(arrs["t_opidx"][ti])]
    if int(arrs["t_type"][ti]) == _TYPE_OK and op.value is not None:
        return op.value
    return ops[int(arrs["t_inv"][ti])].value or []


def _internal_from_flags(ops: list, arrs: dict) -> list[tuple]:
    """Replays the own-append suffix rule for the (rare) reads the C
    flattener flagged: a committed read of a key the same txn appended
    to earlier must end with the txn's own appends, in order."""
    out: list[tuple] = []
    flags = arrs["flag_rd"]
    if not len(flags):
        return out
    for ti in np.unique(arrs["rd_txn"][flags]):
        op = ops[int(arrs["t_opidx"][ti])]
        own: dict = {}
        for mop in _txn_mops(ops, arrs, int(ti)):
            f, k, v = mop[0], mop[1], mop[2]
            if f == "append":
                own.setdefault(k, []).append(v)
            elif f == "r" and v is not None:
                vs = list(v)
                pre = own.get(k)
                if pre and vs[-len(pre):] != pre:
                    out.append((int(ti), k, {
                        "key": k, "expected-suffix": list(pre),
                        "read": vs, "op": op}))
    return out


class Flat:
    """Dense-array view of a list-append history. Two constructors:
    the Python loop over collected Txn objects (reference semantics),
    and from_native() over the C flattener's arrays (native/elleflat.c,
    one C pass over the raw op list — the fast path; differential
    tests pin the two to identical arrays)."""

    @classmethod
    def from_native(cls, ops: list, arrs: dict, keys: list) -> "Flat":
        self = cls.__new__(cls)
        self.n = len(arrs["t_type"])
        self.t_type = arrs["t_type"].astype(np.int8)
        self.t_inv = arrs["t_inv"]
        self.t_comp = arrs["t_comp"]
        self.t_proc = _dense_first_seen(arrs["t_proc"])
        self.t_opidx = arrs["t_opidx"]
        self.key_names = keys
        for f in ("ap_txn", "ap_key", "ap_val", "rd_txn", "rd_key",
                  "rd_len", "re_vals"):
            setattr(self, f, arrs[f])
        self.rd_off = np.concatenate(
            [[0], np.cumsum(self.rd_len)])[:-1].astype(np.int64)
        self.re_read = np.repeat(np.arange(len(self.rd_txn)),
                                 self.rd_len)
        # The C pass flags reads whose txn appended the same key
        # earlier; only those few txns replay the own-suffix rule here.
        self.internal_bad = _internal_from_flags(ops, arrs)
        return self

    def __init__(self, txns: list[Txn]):
        self.txns = txns
        n = len(txns)
        self.n = n
        self.t_type = np.fromiter((_T_CODE[t.type] for t in txns),
                                  dtype=np.int8, count=n)
        self.t_inv = np.fromiter((t.invoke_pos for t in txns),
                                 dtype=np.int64, count=n)
        self.t_comp = np.fromiter((t.complete_pos for t in txns),
                                  dtype=np.int64, count=n)
        proc_ids: dict = {}
        self.t_proc = np.fromiter(
            (proc_ids.setdefault(t.process, len(proc_ids))
             for t in txns), dtype=np.int64, count=n)

        key_ids: dict = {}
        ap_txn: list[int] = []
        ap_key: list[int] = []
        ap_val: list[int] = []
        rd_txn: list[int] = []
        rd_key: list[int] = []
        rd_len: list[int] = []
        re_vals: list[int] = []
        internal_bad: list[tuple] = []  # (txn_i, key_id, record)

        for t in txns:
            own: dict = {}
            consider_reads = t.type == h.OK
            for mop in t.mops:
                f, k, v = mop[0], mop[1], mop[2]
                kid = key_ids.get(k)
                if kid is None:
                    kid = key_ids[k] = len(key_ids)
                if f == "append":
                    if type(v) is not int or not (0 <= v < (1 << _VAL_BITS)):
                        raise Unvectorizable(f"append value {v!r}")
                    ap_txn.append(t.i)
                    ap_key.append(kid)
                    ap_val.append(v)
                    own.setdefault(kid, []).append(v)
                elif f == "r":
                    if v is None or not consider_reads:
                        continue
                    vs = list(v)
                    for x in vs:
                        if type(x) is not int or not (
                                0 <= x < (1 << _VAL_BITS)):
                            raise Unvectorizable(f"read value {x!r}")
                    rd_txn.append(t.i)
                    rd_key.append(kid)
                    rd_len.append(len(vs))
                    re_vals.extend(vs)
                    pre = own.get(kid)
                    if pre and vs[-len(pre):] != pre:
                        internal_bad.append((t.i, kid, {
                            "key": k, "expected-suffix": list(pre),
                            "read": vs, "op": t.op}))
        if len(key_ids) >= (1 << _KEY_BITS):
            raise Unvectorizable("too many keys for pair packing")

        self.key_names = list(key_ids)
        self.ap_txn = np.asarray(ap_txn, dtype=np.int64)
        self.ap_key = np.asarray(ap_key, dtype=np.int64)
        self.ap_val = np.asarray(ap_val, dtype=np.int64)
        self.rd_txn = np.asarray(rd_txn, dtype=np.int64)
        self.rd_key = np.asarray(rd_key, dtype=np.int64)
        self.rd_len = np.asarray(rd_len, dtype=np.int64)
        self.re_vals = np.asarray(re_vals, dtype=np.int64)
        self.rd_off = np.concatenate(
            [[0], np.cumsum(self.rd_len)])[:-1]
        self.re_read = np.repeat(np.arange(len(rd_txn)), self.rd_len)
        self.internal_bad = internal_bad


def _pack(keys: np.ndarray, vals: np.ndarray) -> np.ndarray:
    return (keys << _VAL_BITS) | vals


def _pairs_by_key(a_key, b_key, b_val):
    """(a row, b_val) for every pair of an a row and a b row with the
    same key: a rows in order, each with the b rows of its key in
    theirs."""
    empty = np.empty(0, dtype=np.int64)
    if not len(a_key) or not len(b_key):
        return empty, empty
    ob = np.argsort(b_key, kind="stable")
    bk, bv = b_key[ob], b_val[ob]
    lo = np.searchsorted(bk, a_key, "left")
    reps = np.searchsorted(bk, a_key, "right") - lo
    base = np.repeat(lo, reps)
    step = np.arange(int(reps.sum())) - np.repeat(np.cumsum(reps) - reps,
                                                  reps)
    return np.repeat(np.arange(len(a_key)), reps), bv[base + step]


def _off_spine_py(rd_key, rd_len, sp_off, sp_vals, re_vals):
    """native/order.c's jt_off_spine in numpy: the indices of the read
    elements (each read's rd_len elements following the previous
    read's in re_vals) that differ from the spine element at their
    position, sp_vals[sp_off[key] + j]. The spine is its key's longest
    read, so that element exists."""
    starts = np.cumsum(rd_len) - rd_len
    at = np.repeat(sp_off[rd_key] - starts, rd_len)
    at += np.arange(len(re_vals))
    return np.flatnonzero(sp_vals[at] != re_vals)


class DeviceAppendAnalysis:
    """Mirrors elle.AppendAnalysis over Flat arrays. Flattening runs
    through the C pass (native.elle_flatten) when available; txn/op
    objects materialize lazily, only for anomaly witnesses."""

    _KIND = 0
    _FLAT_CLS = Flat

    def __init__(self, hist: History, device: bool = True):
        self.device = device
        self.txns: list[Txn] | None = None
        self.flat = self._flatten(hist)
        self.anomalies: dict[str, list] = defaultdict(list)
        with telemetry.span("elle.edges"):
            self._spines()
            self._resolve_writers()
            self._read_anomalies()
            data = self._data_edges()
        with telemetry.span("elle.order-edges"):
            (self.edge_src, self.edge_dst, self.edge_ty,
             self.edge_key) = self._with_order_edges(*data)

    def _flatten(self, hist: History):
        with telemetry.span("elle.flatten"):
            self._ops = list(hist)
            try:
                arrs, keys = native.elle_flatten(self._ops, self._KIND)
                telemetry.count("elle.flatten.native")
                return self._FLAT_CLS.from_native(self._ops, arrs, keys)
            except native.NotVectorizable as e:
                raise Unvectorizable(str(e)) from e
            except RuntimeError:
                telemetry.count("elle.flatten.python")
                self.txns = collect(hist)
                return self._FLAT_CLS(self.txns)

    def _op(self, ti: int):
        """The witness op for txn row ti (lazy: no Txn objects on the
        native path)."""
        if self.txns is not None:
            return self.txns[int(ti)].op
        return self._ops[int(self.flat.t_opidx[int(ti)])]

    @property
    def n(self) -> int:
        return self.flat.n

    # -- the certificate of a valid verdict ------------------------------

    def serialization(self) -> np.ndarray | None:
        """Txn rows in an order that serializes a valid verdict's
        history: the committed txns and the :info txns whose appends a
        committed read observed, in a topological order of the
        verdict's own dependency graph (ww, wr, rw, process, realtime)
        plus the two constraints that graph leaves free. An append that
        no read observed follows every read that saw its key's whole
        version order, and an :info txn follows every txn that completed
        before it was invoked. None when the graph so constrained has a
        cycle: then no such order exists."""
        f = self.flat
        took = f.t_type == _TYPE_OK
        # the pids reads observed: a read element on its spine is the
        # spine's pair, so the spine and the elements off it cover them
        seen = np.concatenate([self.sp_pid, self.off_pid])
        if seen.size and len(self.w_txn):
            wt = self.w_txn[seen]
            wt = wt[wt >= 0]
            took[wt[f.t_type[wt] == _TYPE_INFO]] = True
        srcs, dsts = [self.edge_src], [self.edge_dst]
        # appends no read observed, after the reads of the whole spine
        on_spine = np.zeros(len(self.pair_codes), dtype=bool)
        on_spine[self.sp_pid[self.sp_pid >= 0]] = True
        unseen = took[f.ap_txn] & ~on_spine[self.ap_pid]
        whole = f.rd_len == self.spine_len[f.rd_key]
        ia, u_txn = _pairs_by_key(f.rd_key[whole], f.ap_key[unseen],
                                  f.ap_txn[unseen])
        r_txn = f.rd_txn[whole][ia]
        keep = r_txn != u_txn
        srcs.append(r_txn[keep])
        dsts.append(u_txn[keep])
        # realtime into the :info txns that took effect (their own
        # completion is unknown: they never precede by realtime)
        rows = np.flatnonzero(took)
        info = f.t_type[rows] == _TYPE_INFO
        if info.any():
            comp = np.where(info, np.int64(1 << 60), f.t_comp[rows])
            r_src, r_dst = realtime_edges_arrays(f.t_inv[rows], comp)
            into = info[r_dst]
            srcs.append(rows[r_src[into]])
            dsts.append(rows[r_dst[into]])
        order = topo_order(f.n, np.concatenate(srcs), np.concatenate(dsts))
        if len(order) < f.n:
            return None
        return order[took[order]]

    def invocation_indices(self, rows) -> list[int]:
        """The history index of each txn row's invocation."""
        ops = self._ops
        return [ops[p].index for p in self.flat.t_inv[rows].tolist()]

    # -- version orders ----------------------------------------------------

    def _spines(self):
        """Each key's version order, its spine: the longest read of the
        key, the earliest on ties (the host tie-break). It depends only
        on the reads' keys and lengths, so it is chosen before any pair
        is interned; then every read element is checked against the
        spine element at its position."""
        f = self.flat
        R = len(f.rd_txn)
        K = len(f.key_names)
        self.spine_read = np.full(K, -1, dtype=np.int64)
        self.spine_len = np.zeros(K, dtype=np.int64)
        if R:
            order = np.lexsort((np.arange(R), -f.rd_len, f.rd_key))
            first = np.ones(R, dtype=bool)
            kk = f.rd_key[order]
            first[1:] = kk[1:] != kk[:-1]
            sel = order[first]
            keep = f.rd_len[sel] > 0
            self.spine_read[kk[first][keep]] = sel[keep]
            self.spine_len[kk[first][keep]] = f.rd_len[sel][keep]
        # flat spine arrays, gathered from the spine reads' elements
        skey = np.flatnonzero(self.spine_read >= 0)
        srd = self.spine_read[skey]
        lens = f.rd_len[srd]
        off = np.cumsum(lens) - lens
        self.sp_off = np.zeros(K, dtype=np.int64)
        self.sp_off[skey] = off
        self.sp_vals = f.re_vals[np.repeat(f.rd_off[srd] - off, lens)
                                 + np.arange(int(lens.sum()))]
        self.sp_keys = np.repeat(skey, lens)
        # the prefix pass: element j of a read is element j of its key's
        # spine, or the read is incompatible. Only the elements off the
        # spine are interned on their own.
        args = (f.rd_key, f.rd_len, self.sp_off, self.sp_vals, f.re_vals)
        try:
            self.off_elem = native.off_spine(*args)
        except RuntimeError:
            self.off_elem = _off_spine_py(*args)
        self.off_read = f.re_read[self.off_elem]
        telemetry.count("elle.read-elems", len(f.re_vals))
        telemetry.count("elle.read-elems.off-spine", self.off_elem.size)

    # -- writers -----------------------------------------------------------

    def _resolve_writers(self):
        f = self.flat
        A = len(f.ap_txn)
        S = len(self.sp_vals)
        # dense pair ids over every (key, value) an append wrote or a
        # read observed, so value-based lookups (spine successors) work
        # even for values no append wrote (the host engine keys its nxt
        # dict by raw value). A read element on its spine is the spine's
        # pair, so appends, spines and the elements off them cover them.
        codes, pid = np.unique(np.concatenate([
            _pack(f.ap_key, f.ap_val), _pack(self.sp_keys, self.sp_vals),
            _pack(f.rd_key[self.off_read], f.re_vals[self.off_elem])]),
            return_inverse=True)
        self.pair_codes = codes            # sorted unique codes [P]
        P = len(codes)
        self.ap_pid = inv = pid[:A]        # pid per append
        self.sp_pid = pid[A:A + S]         # pid per spine element
        self.off_pid = pid[A + S:]         # pid per element off its spine
        # successor pid along each spine
        self.pair_nxt = np.full(P, -1, dtype=np.int64)
        if S > 1:
            same = self.sp_keys[1:] == self.sp_keys[:-1]
            self.pair_nxt[self.sp_pid[:-1][same]] = self.sp_pid[1:][same]
        order = np.arange(A)
        nonfail = f.t_type[f.ap_txn] != _TYPE_FAIL
        # writer append-row per pid: last non-fail, else first append;
        # pids nothing appended keep w_txn == -1
        last_nf = np.full(P, -1, dtype=np.int64)
        if A:
            np.maximum.at(last_nf, inv[nonfail], order[nonfail])
        first_any = np.full(P, -1, dtype=np.int64)
        if A:
            has = np.zeros(P, dtype=bool)
            has[inv] = True
            first_of = np.full(P, A, dtype=np.int64)
            np.minimum.at(first_of, inv, order)
            first_any[has] = first_of[has]
        w_row = np.where(last_nf >= 0, last_nf, first_any)
        self.w_txn = np.where(w_row >= 0, f.ap_txn[np.clip(w_row, 0, None)]
                              if A else -1, -1)            # [P]
        self.w_fail = np.where(
            self.w_txn >= 0,
            f.t_type[np.clip(self.w_txn, 0, None)] == _TYPE_FAIL,
            False)                                         # [P]
        # j (index among txn's appends to key) and tot, per append row
        grp = np.lexsort((order, f.ap_key, f.ap_txn))
        gk = np.stack([f.ap_txn[grp], f.ap_key[grp]], axis=1)
        new_grp = np.ones(A, dtype=bool)
        if A > 1:
            new_grp[1:] = (gk[1:] != gk[:-1]).any(axis=1)
        grp_id = np.cumsum(new_grp) - 1
        starts = np.flatnonzero(new_grp)
        j_sorted = np.arange(A) - starts[grp_id]
        counts = np.bincount(grp_id, minlength=starts.size)
        tot_sorted = counts[grp_id]
        j = np.empty(A, dtype=np.int64)
        tot = np.empty(A, dtype=np.int64)
        j[grp] = j_sorted
        tot[grp] = tot_sorted
        self.w_j = np.where(w_row >= 0,
                            j[np.clip(w_row, 0, None)] if A else -1, -1)
        self.w_tot = np.where(w_row >= 0,
                              tot[np.clip(w_row, 0, None)] if A else -1,
                              -1)
        # duplicate-appends: non-fail appends beyond the first non-fail
        # of their pid (mirrors the host writer-overwrite rule)
        if A:
            sub = np.flatnonzero(nonfail)
            if sub.size:
                srt = sub[np.argsort(inv[sub], kind="stable")]
                pid_s = inv[srt]
                first_of = np.ones(srt.size, dtype=bool)
                first_of[1:] = pid_s[1:] != pid_s[:-1]
                for row in srt[~first_of]:
                    self.anomalies["duplicate-appends"].append({
                        "key": f.key_names[f.ap_key[row]],
                        "value": int(f.ap_val[row]),
                        "op": self._op(f.ap_txn[row])})
        # possibly-committed writer txns per key (for empty-read rw)
        nf_k = f.ap_key[nonfail]
        nf_t = f.ap_txn[nonfail]
        kt = np.unique(np.stack([nf_k, nf_t], axis=1), axis=0) \
            if nf_k.size else np.empty((0, 2), dtype=np.int64)
        self.wk_key, self.wk_txn = kt[:, 0], kt[:, 1]

    # -- read anomalies ----------------------------------------------------

    def _read_anomalies(self):
        f = self.flat
        # incompatible-order: a read with an element off its spine
        for r in np.unique(self.off_read):
            o, n_ = int(f.rd_off[r]), int(f.rd_len[r])
            k = int(f.rd_key[r])
            so, sl = int(self.sp_off[k]), int(self.spine_len[k])
            self.anomalies["incompatible-order"].append({
                "key": f.key_names[k],
                "read": f.re_vals[o:o + n_].tolist(),
                "spine": self.sp_vals[so:so + sl].tolist(),
                "op": self._op(f.rd_txn[r])})
        # unobservable-read (a writerless pid) and G1a (a failed writer),
        # per element in element order: flagged at each spine position,
        # so only the reads whose prefix holds a flag, or that leave
        # their spine, enumerate their elements
        flag = (self.w_txn[self.sp_pid] < 0) | self.w_fail[self.sp_pid]
        cum = np.concatenate([[0], np.cumsum(flag)])
        lo = self.sp_off[f.rd_key]
        hit = cum[lo + f.rd_len] > cum[lo]
        hit[self.off_read] = True
        self._element_anomalies(np.flatnonzero(hit))
        # G1b: last element is an intermediate version of another txn
        nz = np.flatnonzero(f.rd_len > 0)
        last_pid = self.sp_pid[self.sp_off[f.rd_key[nz]] + f.rd_len[nz] - 1]
        orow = self.off_read
        last = self.off_elem == f.rd_off[orow] + f.rd_len[orow] - 1
        last_pid[np.searchsorted(nz, orow[last])] = self.off_pid[last]
        self.nz_reads = nz
        self.last_pid = last_pid
        if not len(self.w_txn):
            for _ti, _kid, rec in f.internal_bad:
                self.anomalies["internal"].append(rec)
            return
        wi = np.clip(last_pid, 0, None)
        has_w = (last_pid >= 0) & (self.w_txn[wi] >= 0)
        g1b = has_w & (self.w_j[wi] != self.w_tot[wi] - 1) & \
            (self.w_txn[wi] != f.rd_txn[nz])
        for i in np.flatnonzero(g1b):
            r = nz[i]
            o = int(f.rd_off[r] + f.rd_len[r] - 1)
            self.anomalies["G1b"].append({
                "key": f.key_names[f.rd_key[r]],
                "value": int(f.re_vals[o]), "op": self._op(f.rd_txn[r]),
                "writer": self._op(self.w_txn[last_pid[i]])})
        for _ti, _kid, rec in f.internal_bad:
            self.anomalies["internal"].append(rec)

    def _element_anomalies(self, rows):
        """The unobservable-read and G1a records of the elements of read
        rows (ascending), each element with its own pid."""
        f = self.flat
        lens = f.rd_len[rows]
        base = np.cumsum(lens) - lens
        step = np.arange(int(lens.sum())) - np.repeat(base, lens)
        elem = np.repeat(f.rd_off[rows], lens) + step
        rk = np.repeat(rows, lens)
        pid = self.sp_pid[self.sp_off[f.rd_key[rk]] + step]
        pid[np.searchsorted(elem, self.off_elem)] = self.off_pid
        w = self.w_txn[pid]
        for i in np.flatnonzero(w < 0):
            r = rk[i]
            self.anomalies["unobservable-read"].append({
                "key": f.key_names[f.rd_key[r]],
                "value": int(f.re_vals[elem[i]]),
                "op": self._op(f.rd_txn[r])})
        for i in np.flatnonzero(self.w_fail[pid]):
            r = rk[i]
            self.anomalies["G1a"].append({
                "key": f.key_names[f.rd_key[r]],
                "value": int(f.re_vals[elem[i]]),
                "op": self._op(f.rd_txn[r]),
                "writer": self._op(w[i])})

    # -- edges -------------------------------------------------------------

    def _data_edges(self):
        """The ww, wr and rw edges, as per-kind (src, dst, type, key)
        array lists."""
        f = self.flat
        srcs: list[np.ndarray] = []
        dsts: list[np.ndarray] = []
        tys: list[np.ndarray] = []
        kks: list[np.ndarray] = []

        def emit(s, d, ty, kk=None):
            # kk: the key each dependency edge belongs to (-1 for
            # cross-key session/realtime order) — the sharded SCC
            # kernel's key-block layout hint (tpu/scc.py)
            s = np.asarray(s, dtype=np.int64)
            if s.size:
                srcs.append(s)
                dsts.append(np.asarray(d, dtype=np.int64))
                tys.append(np.full(s.size, ty, dtype=np.int64))
                kks.append(np.full(s.size, -1, dtype=np.int64)
                           if kk is None
                           else np.asarray(kk, dtype=np.int64))

        # ww: consecutive distinct valid writers along each spine
        if len(self.w_txn):
            spw = np.where(self.sp_pid >= 0,
                           self.w_txn[np.clip(self.sp_pid, 0, None)], -1)
            valid = (spw >= 0) & ~self.w_fail[
                np.clip(self.sp_pid, 0, None)]
        else:
            spw = np.empty(0, dtype=np.int64)
            valid = np.zeros(len(self.sp_pid), dtype=bool)
        vk = self.sp_keys[valid]
        vt = spw[valid]
        if vt.size > 1:
            same = vk[1:] == vk[:-1]
            diff = vt[1:] != vt[:-1]
            emit(vt[:-1][same & diff], vt[1:][same & diff], WW,
                 vk[1:][same & diff])
        # wr and rw from each non-empty read's last element
        nz, last_pid = self.nz_reads, self.last_pid
        reader = f.rd_txn[nz]
        rkey = f.rd_key[nz]
        if len(self.w_txn):
            wi = np.clip(last_pid, 0, None)
            has_w = (last_pid >= 0) & (self.w_txn[wi] >= 0)
            wr_ok = has_w & (self.w_txn[wi] != reader) & ~self.w_fail[wi]
            emit(self.w_txn[wi[wr_ok]], reader[wr_ok], WR, rkey[wr_ok])
            # nxt is value-based (host keys its dict by raw value), so
            # the anti-dependency fires even when the read's last
            # element itself has no writer (unobservable value)
            nxt = np.where(last_pid >= 0, self.pair_nxt[wi], -1)
            has_n = nxt >= 0
            ni = np.where(has_n, nxt, 0)
            rw_ok = has_n & (self.w_txn[ni] >= 0) & \
                (self.w_txn[ni] != reader) & ~self.w_fail[ni]
            emit(reader[rw_ok], self.w_txn[ni[rw_ok]], RW, rkey[rw_ok])
        # empty reads: rw to first spine writer + off-spine writers
        ez = np.flatnonzero(f.rd_len == 0)
        if ez.size:
            K = len(f.key_names)
            # first valid spine writer per key
            first_w = np.full(K, -1, dtype=np.int64)
            if vt.size:
                rev_k = vk[::-1]
                rev_t = vt[::-1]
                first_w[rev_k] = rev_t  # earliest wins (reverse order)
            # spine writer txn set per (key, txn)
            if vt.size:
                sp_kt = np.unique(np.stack([vk, vt], axis=1), axis=0)
                sp_kt_code = sp_kt[:, 0] * (self.flat.n + 1) + sp_kt[:, 1]
            else:
                sp_kt_code = np.empty(0, dtype=np.int64)
            wk_code = self.wk_key * (self.flat.n + 1) + self.wk_txn
            off_spine = ~np.isin(wk_code, sp_kt_code)
            tk_key = np.concatenate([
                self.wk_key[off_spine],
                np.flatnonzero(first_w >= 0)])
            tk_txn = np.concatenate([
                self.wk_txn[off_spine], first_w[first_w >= 0]])
            ek = f.rd_key[ez]
            ia, er_dst = _pairs_by_key(ek, tk_key, tk_txn)
            er_src = f.rd_txn[ez][ia]
            keep = er_src != er_dst
            emit(er_src[keep], er_dst[keep], RW, ek[ia][keep])
        return srcs, dsts, tys, kks

    def _with_order_edges(self, srcs, dsts, tys, kks):
        """The graph: the data edges, the session order and the
        realtime order (the host engine's sweep, shared), deduplicated
        in first-seen order."""
        comm = np.flatnonzero(self.flat.t_type == _TYPE_OK)
        if comm.size:
            fl = self.flat
            o_src, o_dst, o_ty = order_edges_from_arrays(
                comm, fl.t_inv[comm], fl.t_comp[comm], fl.t_proc[comm])
            if o_src.size:
                srcs.append(o_src)
                dsts.append(o_dst)
                tys.append(o_ty)
                kks.append(np.full(o_src.size, -1, dtype=np.int64))
        if not srcs:
            e = np.empty(0, dtype=np.int64)
            return e, e, e, e
        src = np.concatenate(srcs)
        dst = np.concatenate(dsts)
        ty = np.concatenate(tys)
        kk = np.concatenate(kks)
        code = (src * (self.flat.n + 1) + dst) * 8 + ty
        _, keep = np.unique(code, return_index=True)
        keep.sort()
        return src[keep], dst[keep], ty[keep], kk[keep]


_SUBSETS = ((WW,), (WW, WR), (WW, WR, RW), (WW, WR, RW, PROC),
            (WW, WR, RW, PROC, RT))


def cycle_anomalies_arrays(n: int, src, dst, ty, txns,
                           device: bool = True,
                           ekey=None) -> dict[str, list]:
    """elle.cycle_anomalies over edge arrays: SCCs per cumulative edge
    subset via the device kernel, witnesses extracted host-side. txns
    is either a Txn list or a callable ti -> witness op (the lazy
    accessor of the native flattening path). ekey: per-edge key ids
    for the sharded SCC kernel's key-block edge layout."""
    op_of = txns if callable(txns) else (lambda i: txns[i].op)
    out: dict[str, list] = defaultdict(list)
    if not len(src):
        return out
    from . import spmd

    if (ekey is not None and len(ekey) == len(src) and device
            and spmd.spmd_devices() > 1
            and len(src) >= scc_mod.DEVICE_MIN_EDGES):
        # order edges into key blocks ONCE — up to six SCC launches
        # below share the same edge array (only the subset mask
        # differs), and scc_device skips its own argsort when the
        # array is already key-sorted. Gated on the same conditions as
        # scc_device's sharded path: anywhere else the layout is never
        # consumed and the sort+copies would be pure overhead.
        order = np.argsort(np.asarray(ekey), kind="stable")
        src = np.asarray(src)[order]
        dst = np.asarray(dst)[order]
        ty = np.asarray(ty)[order]
        ekey = np.asarray(ekey)[order]
    # Early exit: subset edges are subsets of the full graph, so a
    # clean full graph proves every graded subset clean too — valid
    # histories cost ONE device SCC instead of five.
    with telemetry.span("elle.scc"):
        full = scc_mod.scc(n, src, dst, device=device, ekey=ekey)
    if not scc_mod.nontrivial_from_labels(full):
        return out
    with telemetry.span("elle.cycles"):
        _graded_cycles(out, n, src, dst, ty, full, op_of, device, ekey)
    return out


def _graded_cycles(out: dict, n: int, src, dst, ty, full, op_of,
                   device: bool, ekey) -> None:
    """The SCCs of each graded subset, a witness cycle per new
    component, classified."""
    seen: set = set()
    for sub in _SUBSETS:
        # boolean mask over ONE shared edge array: every subset reuses
        # the same compiled kernel shape bucket. The final subset is
        # the full graph, already solved above.
        mask = np.isin(ty, sub)
        if not mask.any():
            continue
        if sub == _SUBSETS[-1]:
            labels = full
        else:
            with telemetry.span("elle.scc"):
                labels = scc_mod.scc(n, src, dst, emask=mask,
                                     device=device, ekey=ekey)
        groups = scc_mod.nontrivial_from_labels(labels)
        for members in groups:
            key = frozenset(int(x) for x in members)
            if key in seen:
                continue
            seen.add(key)
            em = mask & np.isin(src, members) & np.isin(dst, members)
            edges = [(int(a), int(b), int(c))
                     for a, b, c in zip(src[em], dst[em], ty[em])]
            cycle = _find_cycle(sorted(int(x) for x in members), edges)
            if not cycle:
                continue
            name = _classify(cycle)
            out[name].append({
                "cycle": [op_of(a) for a, _b, _c in cycle],
                "steps": [{"from": a, "to": b, "type": EDGE_NAMES[c]}
                          for a, b, c in cycle]})


def check_list_append_device(hist, device: bool = True) -> dict:
    """Drop-in device-path analog of elle.check_list_append. Raises
    Unvectorizable when the history can't be interned."""
    return analyze_list_append(hist, device)[0]


def analyze_list_append(hist, device: bool = True):
    """(result, DeviceAppendAnalysis): check_list_append_device's
    verdict and the analysis behind it, whose dependency graph orders a
    valid verdict's certificate. Raises Unvectorizable when the history
    can't be interned."""
    if not isinstance(hist, History):
        hist = History(hist)
    prof = profiler.get()
    rec = prof.begin("elle-append")
    with prof.phase(rec, "encode_ns"):
        # host side: flatten + edge inference (the SCC launches
        # inside cycle_anomalies_arrays profile themselves)
        a = DeviceAppendAnalysis(hist, device=device)
    telemetry.count("elle.txns", a.flat.n)
    telemetry.count("elle.edges", int(len(a.edge_src)))
    rec.update(txns=a.flat.n, edges=int(len(a.edge_src)))
    anomalies = dict(a.anomalies)
    with prof.phase(rec, "compute_ns"):
        for name, ws in cycle_anomalies_arrays(
                a.flat.n, a.edge_src, a.edge_dst, a.edge_ty, a._op,
                device=device, ekey=a.edge_key).items():
            anomalies[name] = ws
    prof.finish(rec)
    return {
        "valid?": not anomalies,
        "anomaly-types": sorted(anomalies.keys()),
        "anomalies": {k: v[:8] for k, v in anomalies.items()},
        "edge-count": int(len(a.edge_src)),
        "txn-count": a.flat.n,
    }, a


# ---------------------------------------------------------------------------
# rw-register device path
# ---------------------------------------------------------------------------

class RwFlat:
    """Dense-array view of a write/read-register history (the
    rw-register analog of Flat). One Python pass collects writes
    (all txn types — they all claim writer slots), committed reads,
    write-follows-read pairs, external reads, and the per-txn internal
    anomalies; everything downstream is numpy over packed (key, value)
    codes."""

    @classmethod
    def from_native(cls, ops: list, arrs: dict, keys: list) -> "RwFlat":
        self = cls.__new__(cls)
        self.n = len(arrs["t_type"])
        self.t_type = arrs["t_type"].astype(np.int8)
        self.t_inv = arrs["t_inv"]
        self.t_comp = arrs["t_comp"]
        self.t_proc = _dense_first_seen(arrs["t_proc"])
        self.t_opidx = arrs["t_opidx"]
        self.key_names = keys
        for f in ("wr_txn", "wr_key", "wr_val", "wr_nonfinal",
                  "rd_txn", "rd_key", "rd_val",
                  "fr_txn", "fr_key", "fr_prev", "fr_new",
                  "er_txn", "er_key", "er_val"):
            setattr(self, f, arrs[f])
        # internal anomalies: the C pass records (read row, expected)
        self.internal_bad = [
            {"key": keys[int(self.rd_key[r])],
             "expected": int(e), "read": int(self.rd_val[r]),
             "op": ops[int(arrs["t_opidx"][self.rd_txn[r]])]}
            for r, e in zip(arrs["int_row"], arrs["int_expected"])]
        return self

    def __init__(self, txns: list[Txn]):
        self.txns = txns
        n = len(txns)
        self.t_type = np.fromiter((_T_CODE[t.type] for t in txns),
                                  dtype=np.int8, count=n)
        self.t_inv = np.fromiter((t.invoke_pos for t in txns),
                                 dtype=np.int64, count=n)
        self.t_comp = np.fromiter((t.complete_pos for t in txns),
                                  dtype=np.int64, count=n)
        proc_ids: dict = {}
        self.t_proc = np.fromiter(
            (proc_ids.setdefault(t.process, len(proc_ids))
             for t in txns), dtype=np.int64, count=n)
        key_ids: dict = {}
        wr_txn: list[int] = []
        wr_key: list[int] = []
        wr_val: list[int] = []
        wr_nonfinal: list[int] = []  # row indices of non-final writes
        rd_txn: list[int] = []
        rd_key: list[int] = []
        rd_val: list[int] = []
        fr_txn: list[int] = []       # write-follows-read rows
        fr_key: list[int] = []
        fr_prev: list[int] = []
        fr_new: list[int] = []
        er_txn: list[int] = []       # external reads
        er_key: list[int] = []
        er_val: list[int] = []
        internal_bad: list[dict] = []

        def check_val(v):
            if type(v) is not int or not (0 <= v < (1 << _VAL_BITS)):
                raise Unvectorizable(f"register value {v!r}")

        for t in txns:
            ok = t.type == h.OK
            nonfail = t.type != h.FAIL
            expected: dict = {}
            last_read: dict = {}
            written: set = set()
            er_seen: set = set()
            per_key_rows: dict = {}
            for mop in t.mops:
                f, k, v = mop[0], mop[1], mop[2]
                kid = key_ids.get(k)
                if kid is None:
                    kid = key_ids[k] = len(key_ids)
                if f == "w":
                    check_val(v)
                    row = len(wr_txn)
                    wr_txn.append(t.i)
                    wr_key.append(kid)
                    wr_val.append(v)
                    if nonfail:
                        per_key_rows.setdefault(kid, []).append(row)
                    if ok:
                        pv = last_read.pop(kid, None)
                        if pv is not None:
                            fr_txn.append(t.i)
                            fr_key.append(kid)
                            fr_prev.append(pv)
                            fr_new.append(v)
                        expected[kid] = v
                    written.add(kid)
                elif f == "r" and ok:
                    if v is None:
                        # A None first read IS the key's external read
                        # (txnlib.ext_reads records it; the host rw
                        # pass then skips the key) — a later valued
                        # read must NOT be promoted to external
                        if kid not in written:
                            er_seen.add(kid)
                        continue
                    check_val(v)
                    rd_txn.append(t.i)
                    rd_key.append(kid)
                    rd_val.append(v)
                    if kid in expected and expected[kid] != v:
                        internal_bad.append(
                            {"key": k, "expected": expected[kid],
                             "read": v, "op": t.op})
                    expected[kid] = v
                    last_read[kid] = v
                    if kid not in written and kid not in er_seen:
                        er_seen.add(kid)
                        er_txn.append(t.i)
                        er_key.append(kid)
                        er_val.append(v)
            # non-final writes per key (txn.clj: intermediates)
            for rows in per_key_rows.values():
                wr_nonfinal.extend(rows[:-1])
        if len(key_ids) >= (1 << _KEY_BITS):
            raise Unvectorizable("too many keys for pair packing")
        self.key_names = list(key_ids)
        self.wr_txn = np.asarray(wr_txn, dtype=np.int64)
        self.wr_key = np.asarray(wr_key, dtype=np.int64)
        self.wr_val = np.asarray(wr_val, dtype=np.int64)
        self.wr_nonfinal = np.asarray(wr_nonfinal, dtype=np.int64)
        self.rd_txn = np.asarray(rd_txn, dtype=np.int64)
        self.rd_key = np.asarray(rd_key, dtype=np.int64)
        self.rd_val = np.asarray(rd_val, dtype=np.int64)
        self.fr_txn = np.asarray(fr_txn, dtype=np.int64)
        self.fr_key = np.asarray(fr_key, dtype=np.int64)
        self.fr_prev = np.asarray(fr_prev, dtype=np.int64)
        self.fr_new = np.asarray(fr_new, dtype=np.int64)
        self.er_txn = np.asarray(er_txn, dtype=np.int64)
        self.er_key = np.asarray(er_key, dtype=np.int64)
        self.er_val = np.asarray(er_val, dtype=np.int64)
        self.internal_bad = internal_bad
        self.n = n


class DeviceRwAnalysis:
    """Vectorized analog of elle.check_rw_register's per-txn dict
    passes: writer resolution, duplicate/aborted/intermediate read
    anomalies, and wr/ww/rw edge inference as packed-array lookups.
    Witness payloads for flagged rows are extracted host-side, capped
    at the same 8 the result slice keeps."""

    CAP = 8

    _KIND = 1
    _FLAT_CLS = RwFlat

    def __init__(self, hist: History, device: bool = True):
        self.device = device
        self.txns: list[Txn] | None = None
        f = self.flat = self._flatten(hist)
        self.anomalies: dict[str, list] = defaultdict(list)
        for rec in f.internal_bad:
            self.anomalies["internal"].append(rec)
        self._resolve_writers()
        self._read_anomalies_and_edges()

    _flatten = DeviceAppendAnalysis._flatten
    _op = DeviceAppendAnalysis._op

    def _resolve_writers(self):
        f = self.flat
        W = len(f.wr_txn)
        codes = np.unique(_pack(f.wr_key, f.wr_val)) if W else \
            np.empty(0, dtype=np.int64)
        self.pair_codes = codes
        P = len(codes)
        inv = (np.searchsorted(codes, _pack(f.wr_key, f.wr_val))
               if W else np.empty(0, dtype=np.int64))
        order = np.arange(W)
        nonfail = f.t_type[f.wr_txn] != _TYPE_FAIL if W else \
            np.empty(0, dtype=bool)
        # writer row per pair: last non-fail write, else first write
        # (the host's writer-dict overwrite rule)
        last_nf = np.full(P, -1, dtype=np.int64)
        first_any = np.full(P, W, dtype=np.int64)
        if W:
            np.maximum.at(last_nf, inv[nonfail], order[nonfail])
            np.minimum.at(first_any, inv, order)
        w_row = np.where(last_nf >= 0, last_nf, first_any)
        self.w_txn = (f.wr_txn[np.clip(w_row, 0, max(W - 1, 0))]
                      if W else np.empty(0, dtype=np.int64))
        self.w_fail = (f.t_type[self.w_txn] == _TYPE_FAIL
                       if W else np.empty(0, dtype=bool))
        # duplicate-writes: non-fail writes beyond their pair's first
        # non-fail (host flags when the standing writer is non-fail)
        if W:
            sub = np.flatnonzero(nonfail)
            if sub.size:
                srt = sub[np.argsort(inv[sub], kind="stable")]
                pid_s = inv[srt]
                first = np.ones(srt.size, dtype=bool)
                first[1:] = pid_s[1:] != pid_s[:-1]
                for row in srt[~first][:self.CAP]:
                    self.anomalies["duplicate-writes"].append({
                        "key": f.key_names[f.wr_key[row]],
                        "value": int(f.wr_val[row]),
                        "op": self._op(f.wr_txn[row])})
        # intermediate (non-final) writer per pair: last row in txn
        # order wins, like the host's dict overwrite
        self.inter_txn = np.full(P, -1, dtype=np.int64)
        if len(f.wr_nonfinal):
            rows = f.wr_nonfinal
            pids = inv[rows]
            np.maximum.at(self.inter_txn, pids, rows)
            got = self.inter_txn >= 0
            self.inter_txn[got] = f.wr_txn[self.inter_txn[got]]

    def _pid_of(self, keys, vals) -> np.ndarray:
        codes = _pack(np.asarray(keys, dtype=np.int64),
                      np.asarray(vals, dtype=np.int64))
        if len(self.pair_codes) == 0:
            return np.full(len(codes), -1, dtype=np.int64)
        pos = np.searchsorted(self.pair_codes, codes)
        pos = np.clip(pos, 0, len(self.pair_codes) - 1)
        return np.where(self.pair_codes[pos] == codes, pos, -1)

    def _read_anomalies_and_edges(self):
        f = self.flat
        src: list = []
        dst: list = []
        ty: list = []
        kks: list = []

        def emit(s, d, t, kk=None):
            src.append(np.asarray(s, dtype=np.int64))
            dst.append(np.asarray(d, dtype=np.int64))
            ty.append(np.full(len(s), t, dtype=np.int64))
            kks.append(np.full(len(s), -1, dtype=np.int64)
                       if kk is None
                       else np.asarray(kk, dtype=np.int64))

        # -- reads: unobservable / G1a / G1b + wr edges
        if len(f.rd_txn):
            pid = self._pid_of(f.rd_key, f.rd_val)
            missing = pid == -1
            for i in np.flatnonzero(missing)[:self.CAP]:
                self.anomalies["unobservable-read"].append({
                    "key": f.key_names[f.rd_key[i]],
                    "value": int(f.rd_val[i]),
                    "op": self._op(f.rd_txn[i])})
            found = ~missing
            if len(self.pair_codes):
                wt = np.where(found,
                              self.w_txn[np.clip(pid, 0, None)], -1)
                wfail = np.where(
                    found, self.w_fail[np.clip(pid, 0, None)], False)
            else:  # reads but not a single write anywhere
                wt = np.full(len(f.rd_txn), -1, dtype=np.int64)
                wfail = np.zeros(len(f.rd_txn), dtype=bool)
            g1a = found & wfail
            for i in np.flatnonzero(g1a)[:self.CAP]:
                self.anomalies["G1a"].append({
                    "key": f.key_names[f.rd_key[i]],
                    "value": int(f.rd_val[i]),
                    "op": self._op(f.rd_txn[i]),
                    "writer": self._op(wt[i])})
            ext = found & ~wfail & (wt != f.rd_txn)
            inter = np.where(found,
                             self.inter_txn[np.clip(pid, 0, None)], -1)
            g1b = ext & (inter >= 0) & (inter != f.rd_txn)
            for i in np.flatnonzero(g1b)[:self.CAP]:
                self.anomalies["G1b"].append({
                    "key": f.key_names[f.rd_key[i]],
                    "value": int(f.rd_val[i]),
                    "op": self._op(f.rd_txn[i]),
                    "writer": self._op(inter[i])})
            emit(wt[ext], f.rd_txn[ext], WR, f.rd_key[ext])

        # -- write-follows-read: ww edges + version succession
        if len(f.fr_txn):
            pw_pid = self._pid_of(f.fr_key, f.fr_prev)
            ok = pw_pid >= 0
            pw = np.where(ok, self.w_txn[np.clip(pw_pid, 0, None)], -1)
            m = ok & (pw >= 0) & (pw != f.fr_txn)
            emit(pw[m], f.fr_txn[m], WW, f.fr_key[m])
            # succ[(k, prev)] = new, last in txn order wins
            fp = _pack(f.fr_key, f.fr_prev)
            order = np.argsort(fp, kind="stable")
            fp_s = fp[order]
            last = np.ones(len(fp_s), dtype=bool)
            last[:-1] = fp_s[1:] != fp_s[:-1]
            self.succ_codes = fp_s[last]
            self.succ_vals = f.fr_new[order][last]
        else:
            self.succ_codes = np.empty(0, dtype=np.int64)
            self.succ_vals = np.empty(0, dtype=np.int64)

        # -- external reads -> rw edges against the proven successor
        if len(f.er_txn) and len(self.succ_codes):
            ec = _pack(f.er_key, f.er_val)
            pos = np.searchsorted(self.succ_codes, ec)
            pos = np.clip(pos, 0, len(self.succ_codes) - 1)
            has = self.succ_codes[pos] == ec
            nv = np.where(has, self.succ_vals[pos], 0)
            w2_pid = self._pid_of(f.er_key, nv)
            w2_ok = has & (w2_pid >= 0)
            w2 = np.where(w2_ok,
                          self.w_txn[np.clip(w2_pid, 0, None)], -1)
            m = (w2_ok & (w2 >= 0) & (w2 != f.er_txn)
                 & (f.t_type[np.clip(w2, 0, None)] == _TYPE_OK))
            emit(f.er_txn[m], w2[m], RW, f.er_key[m])

        fl = self.flat
        comm = np.flatnonzero(fl.t_type == _TYPE_OK)
        o_src, o_dst, o_ty = order_edges_from_arrays(
            comm, fl.t_inv[comm], fl.t_comp[comm], fl.t_proc[comm])
        src.append(o_src)
        dst.append(o_dst)
        ty.append(o_ty)
        kks.append(np.full(o_src.size, -1, dtype=np.int64))
        self.edge_src = np.concatenate(src) if src else \
            np.empty(0, dtype=np.int64)
        self.edge_dst = np.concatenate(dst) if dst else \
            np.empty(0, dtype=np.int64)
        self.edge_ty = np.concatenate(ty) if ty else \
            np.empty(0, dtype=np.int64)
        self.edge_key = np.concatenate(kks) if kks else \
            np.empty(0, dtype=np.int64)



def check_rw_register_device(hist, device: bool = True) -> dict:
    """Drop-in device-path analog of elle.check_rw_register. Raises
    Unvectorizable when the history can't be interned."""
    if not isinstance(hist, History):
        hist = History(hist)
    prof = profiler.get()
    rec = prof.begin("elle-rw")
    with telemetry.span("elle:rw-register") as sp:
        with prof.phase(rec, "encode_ns"):
            a = DeviceRwAnalysis(hist, device=device)
        if sp is not None:
            sp["attrs"] = {"txns": a.flat.n,
                           "edges": int(len(a.edge_src))}
    telemetry.count("elle.txns", a.flat.n)
    telemetry.count("elle.edges", int(len(a.edge_src)))
    rec.update(txns=a.flat.n, edges=int(len(a.edge_src)))
    anomalies = dict(a.anomalies)
    with prof.phase(rec, "compute_ns"):
        for name, ws in cycle_anomalies_arrays(
                a.flat.n, a.edge_src, a.edge_dst, a.edge_ty, a._op,
                device=device, ekey=a.edge_key).items():
            anomalies[name] = ws
    prof.finish(rec)
    return {
        "valid?": not anomalies,
        "anomaly-types": sorted(anomalies.keys()),
        "anomalies": {k: v[:8] for k, v in anomalies.items()},
        "edge-count": int(len(a.edge_src)),
        "txn-count": a.flat.n,
    }
