"""Differential tests: the device-path list-append engine
(tpu/elle_device) must agree with the host reference engine
(tpu/elle) on every fixture and on randomized valid/corrupted
histories, mirroring how the reference treats elle as ground truth
(jepsen/src/jepsen/tests/cycle/append.clj)."""

import itertools
import random

import numpy as np
import pytest

from jepsen_tpu import native
from jepsen_tpu.history import History, op
from jepsen_tpu.tpu import elle, elle_device, scc as scc_mod
from jepsen_tpu.tpu.elle import realtime_edges_arrays, topo_order
from jepsen_tpu.tpu.elle_device import (_TYPE_FAIL, _TYPE_INFO, _TYPE_OK,
                                        _pack, _pairs_by_key)


def T(*events):
    return History([op(type=t, process=p, f="txn", value=m)
                    for t, p, m in events])


FIXTURES = {
    "valid_seq": T(
        ("invoke", 0, [["append", "x", 1]]), ("ok", 0, [["append", "x", 1]]),
        ("invoke", 1, [["r", "x", None]]), ("ok", 1, [["r", "x", [1]]]),
        ("invoke", 0, [["append", "x", 2]]), ("ok", 0, [["append", "x", 2]]),
        ("invoke", 1, [["r", "x", None]]), ("ok", 1, [["r", "x", [1, 2]]])),
    "g0": T(("invoke", 0, [["append", "x", 1], ["append", "y", 1]]),
            ("invoke", 1, [["append", "x", 2], ["append", "y", 2]]),
            ("ok", 0, [["append", "x", 1], ["append", "y", 1]]),
            ("ok", 1, [["append", "x", 2], ["append", "y", 2]]),
            ("invoke", 2, [["r", "x", None], ["r", "y", None]]),
            ("ok", 2, [["r", "x", [1, 2]], ["r", "y", [2, 1]]])),
    "g1a": T(("invoke", 0, [["append", "x", 9]]),
             ("fail", 0, [["append", "x", 9]]),
             ("invoke", 1, [["r", "x", None]]),
             ("ok", 1, [["r", "x", [9]]])),
    "g1b": T(("invoke", 0, [["append", "x", 1], ["append", "x", 2]]),
             ("ok", 0, [["append", "x", 1], ["append", "x", 2]]),
             ("invoke", 1, [["r", "x", None]]),
             ("ok", 1, [["r", "x", [1]]])),
    "g1c": T(("invoke", 0, [["append", "x", 1], ["r", "y", None]]),
             ("invoke", 1, [["append", "y", 1], ["r", "x", None]]),
             ("ok", 0, [["append", "x", 1], ["r", "y", [1]]]),
             ("ok", 1, [["append", "y", 1], ["r", "x", [1]]])),
    "g_single": T(("invoke", 0, [["r", "x", None], ["r", "y", None]]),
                  ("invoke", 1, [["append", "y", 1], ["append", "x", 1]]),
                  ("ok", 1, [["append", "y", 1], ["append", "x", 1]]),
                  ("ok", 0, [["r", "x", []], ["r", "y", [1]]]),
                  ("invoke", 2, [["r", "x", None]]),
                  ("ok", 2, [["r", "x", [1]]])),
    "g2": T(("invoke", 0, [["r", "x", None], ["append", "y", 1]]),
            ("invoke", 1, [["r", "y", None], ["append", "x", 1]]),
            ("ok", 0, [["r", "x", []], ["append", "y", 1]]),
            ("ok", 1, [["r", "y", []], ["append", "x", 1]]),
            ("invoke", 2, [["r", "x", None], ["r", "y", None]]),
            ("ok", 2, [["r", "x", [1]], ["r", "y", [1]]])),
    "incompat": T(("invoke", 0, [["r", "x", None]]),
                  ("ok", 0, [["r", "x", [1, 2]]]),
                  ("invoke", 1, [["r", "x", None]]),
                  ("ok", 1, [["r", "x", [2, 1, 3]]])),
    # the last element leaves the spine for an :info append no other
    # read observed: the txn took effect, so the serialization holds it
    "incompat_info_last": T(
        ("invoke", 0, [["append", "x", 1]]), ("ok", 0, [["append", "x", 1]]),
        ("invoke", 1, [["append", "x", 2]]), ("ok", 1, [["append", "x", 2]]),
        ("invoke", 5, [["append", "x", 3]]), ("ok", 5, [["append", "x", 3]]),
        ("invoke", 2, [["append", "x", 7]]), ("info", 2, [["append", "x", 7]]),
        ("invoke", 3, [["r", "x", None]]), ("ok", 3, [["r", "x", [1, 2, 3]]]),
        ("invoke", 4, [["r", "x", None]]), ("ok", 4, [["r", "x", [1, 7]]])),
    "internal": T(("invoke", 0, [["append", "x", 5], ["r", "x", None]]),
                  ("ok", 0, [["append", "x", 5], ["r", "x", [1]]])),
    "dup": T(("invoke", 0, [["append", "x", 1]]),
             ("ok", 0, [["append", "x", 1]]),
             ("invoke", 1, [["append", "x", 1]]),
             ("ok", 1, [["append", "x", 1]])),
    "retry_after_fail": T(
        ("invoke", 0, [["append", "x", 1]]), ("fail", 0, [["append", "x", 1]]),
        ("invoke", 0, [["append", "x", 1]]), ("ok", 0, [["append", "x", 1]]),
        ("invoke", 1, [["r", "x", None]]), ("ok", 1, [["r", "x", [1]]])),
    "info_observed": T(
        ("invoke", 0, [["append", "x", 1]]), ("info", 0, [["append", "x", 1]]),
        ("invoke", 1, [["r", "x", None]]), ("ok", 1, [["r", "x", [1]]])),
    "empty_read_info": T(
        ("invoke", 0, [["append", "k", 1]]), ("info", 0, [["append", "k", 1]]),
        ("invoke", 1, [["r", "k", None]]), ("ok", 1, [["r", "k", [1]]]),
        ("invoke", 2, [["r", "k", None]]), ("ok", 2, [["r", "k", []]])),
    "rt_beyond": T(
        ("invoke", 1, [["append", "z", 1]]), ("invoke", 0, [["append", "y", 1]]),
        ("ok", 0, [["append", "y", 1]]), ("ok", 1, [["append", "z", 1]]),
        ("invoke", 2, [["r", "y", None]]), ("ok", 2, [["r", "y", []]])),
    "empty": T(),
    "no_appends": T(("invoke", 0, [["r", "x", None]]),
                    ("ok", 0, [["r", "x", []]])),
}


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_fixture_matches_host(name):
    hist = FIXTURES[name]
    rh = elle.check_list_append(hist, {"engine": "host"})
    rd = elle_device.check_list_append_device(hist)
    assert rh["valid?"] == rd["valid?"], (rh, rd)
    assert rh["anomaly-types"] == rd["anomaly-types"], (rh, rd)


def gen_history(rng, n_txns, n_keys=8, max_len=4, rotate=24):
    """Concurrent valid-by-construction list-append history with
    ok/fail/info completions and key rotation."""
    store = {}
    epoch = [0]
    events = []
    open_t = {}
    procs = list(range(5))
    t_count = 0
    nv = [1]
    while t_count < n_txns or open_t:
        idle = [p for p in procs if p not in open_t]
        if t_count < n_txns and idle and (rng.random() < 0.6
                                          or not open_t):
            p = rng.choice(idle)
            txn = []
            for _ in range(rng.randint(1, max_len)):
                k = f"k{rng.randrange(n_keys)}e{epoch[0]}"
                if rng.random() < 0.5:
                    txn.append(["append", k, nv[0]])
                    nv[0] += 1
                else:
                    txn.append(["r", k, None])
            events.append(("invoke", p, txn))
            open_t[p] = txn
            t_count += 1
            if t_count % rotate == 0:
                epoch[0] += 1
        else:
            p = rng.choice(list(open_t))
            txn = open_t.pop(p)
            r = rng.random()
            if r < 0.85:
                res = []
                for f, k, v in txn:
                    if f == "append":
                        store.setdefault(k, []).append(v)
                        res.append(["append", k, v])
                    else:
                        res.append(["r", k, list(store.get(k, []))])
                events.append(("ok", p, res))
            elif r < 0.95:
                events.append(("fail", p, txn))
            else:
                if rng.random() < 0.5:
                    for f, k, v in txn:
                        if f == "append":
                            store.setdefault(k, []).append(v)
                events.append(("info", p, txn))
    return [op(type=t, process=p, f="txn", value=m)
            for t, p, m in events]


def corrupt(rng, ops):
    """Damage one committed read to seed an anomaly."""
    ops = [op(**o.to_dict()) for o in ops]
    mode = rng.choice(["drop_elem", "swap", "phantom", "truncate"])
    oks = [i for i, o in enumerate(ops)
           if o.type == "ok" and any(m[0] == "r" and m[2]
                                     for m in (o.value or []))]
    if not oks:
        return ops
    i = rng.choice(oks)
    v = [list(m) for m in ops[i].value]
    for m in v:
        if m[0] == "r" and m[2]:
            lst = list(m[2])
            if mode == "drop_elem" and len(lst) > 1:
                del lst[rng.randrange(len(lst) - 1)]
            elif mode == "swap" and len(lst) > 1:
                a, b = rng.sample(range(len(lst)), 2)
                lst[a], lst[b] = lst[b], lst[a]
            elif mode == "phantom":
                lst.append(999999999)
            elif mode == "truncate" and len(lst) > 1:
                lst = lst[:-1]
            m[2] = lst
            break
    ops[i] = op(**{**ops[i].to_dict(), "value": v})
    return ops


def planted_history(seed, n_txns=300):
    """A concurrent list-append history with every anomaly the read
    pass resolves planted in it: failed appends the store applied
    anyway and phantom values (G1a and unobservable elements inside
    later reads' prefixes), reads of a txn's intermediate append (G1b),
    re-appended values (duplicate appends), reads that miss their own
    txn's appends (internal), empty reads of fresh keys, keys no read
    observes, and two reads that leave their spine: one mid-read, one
    at its last element, which reads an :info append that took no
    effect where there is one (incompatible order)."""
    rng = random.Random(seed)
    store: dict = {}
    events: list = []
    open_: dict = {}
    nv = itertools.count(1)
    phantom = itertools.count(10 ** 6)
    lost: dict = {}  # key -> values of :info appends that took no effect
    t = 0
    while t < n_txns or open_:
        idle = [p for p in range(4) if p not in open_]
        if t < n_txns and idle and (rng.random() < 0.6 or not open_):
            p = rng.choice(idle)
            epoch = t // 40
            txn = []
            for _ in range(rng.randint(1, 4)):
                k = f"k{rng.randrange(3)}e{epoch}"
                x = rng.random()
                if x < 0.1:
                    txn.append(["append", f"w{epoch}", next(nv)])
                elif x < 0.15 and store.get(k):
                    txn.append(["append", k, rng.choice(store[k])])
                elif x < 0.55:
                    txn.append(["append", k, next(nv)])
                else:
                    txn.append(["r", k, None])
            events.append(("invoke", p, txn))
            open_[p] = txn
            t += 1
            continue
        p = rng.choice(list(open_))
        txn = open_.pop(p)
        x = rng.random()
        if x < 0.1 or x < 0.18 and rng.random() < 0.5:
            # :fail applied anyway (G1a), or an :info that took effect
            for f, k, v in txn:
                if f == "append":
                    store.setdefault(k, []).append(v)
            events.append(("fail" if x < 0.1 else "info", p, txn))
            continue
        if x < 0.18:
            for f, k, v in txn:
                if f == "append":
                    lost.setdefault(k, []).append(v)
            events.append(("info", p, txn))
            continue
        res, own = [], {}
        for f, k, v in txn:
            lst = store.setdefault(k, [])
            if f == "append":
                lst.append(v)
                own.setdefault(k, []).append(v)
                res.append([f, k, v])
            elif own.get(k) and rng.random() < 0.2:
                res.append([f, k, lst[:-1]])  # misses its own append
            else:
                if rng.random() < 0.03:
                    lst.append(next(phantom))  # a value no one appended
                res.append([f, k, list(lst)])
        events.append(("ok", p, res))
        for k, vs in own.items():
            if len(vs) > 1 and rng.random() < 0.5:
                # another process reads the intermediate version
                events += [("invoke", 9, [["r", k, None]]),
                           ("ok", 9, [["r", k, store[k][:-1]]])]
    # two committed reads shorter than their key's longest leave it
    longest: dict = {}
    reads = []
    for i, (ty, _p, mops) in enumerate(events):
        for j, (f, k, v) in enumerate(mops):
            if ty == "ok" and f == "r":
                longest[k] = max(longest.get(k, 0), len(v))
                reads.append((i, j))
    cands = [(i, j) for i, j in reads
             if 3 <= len(events[i][2][j][2]) < longest[events[i][2][j][1]]]
    rng.shuffle(cands)
    mid = next((i, j) for i, j in cands
               if events[i][2][j][2][0] != events[i][2][j][2][1])
    rest = [c for c in cands if c != mid]
    last = next((c for c in rest if events[c[0]][2][c[1]][1] in lost),
                rest[0])
    for (i, j), mode in ((mid, "mid"), (last, "last")):
        ty, p, mops = events[i]
        mops = [list(m) for m in mops]
        lst = list(mops[j][2])
        if mode == "mid":
            lst[0], lst[1] = lst[1], lst[0]
        else:
            lst[-1] = lost.get(mops[j][1], [next(nv)])[0]
        mops[j][2] = lst
        events[i] = (ty, p, mops)
    return History([op(type=ty, process=p, f="txn", value=m)
                    for ty, p, m in events])


class GlobalSortAnalysis(elle_device.DeviceAppendAnalysis):
    """The interning that spine positions replaced, kept as the
    differential reference: the pair universe is one global sort of
    the appends and every read element, each element takes its pid by
    binary search, and the read anomalies are read per element."""

    def _spines(self):
        pass  # chosen below, once every element has a pid

    def serialization(self) -> np.ndarray | None:
        """The serialization as the global sort gave it its observed
        pids: every read element's."""
        f = self.flat
        took = f.t_type == _TYPE_OK
        seen = self.re_pid[self.re_pid >= 0] if len(self.re_pid) else \
            np.empty(0, dtype=np.int64)
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

    def _resolve_writers(self):
        f = self.flat
        A = len(f.ap_txn)
        ap_code = _pack(f.ap_key, f.ap_val)
        re_code = (_pack(f.rd_key[f.re_read], f.re_vals)
                   if len(f.re_vals) else np.empty(0, dtype=np.int64))
        # dense pair ids over appends AND read elements, so value-based
        # lookups (spine successors) work even for values no append
        # wrote (the host engine keys its nxt dict by raw value)
        codes = np.unique(np.concatenate([ap_code, re_code]))
        self.pair_codes = codes            # sorted unique codes [P]
        P = len(codes)
        inv = np.searchsorted(codes, ap_code)
        self.ap_pid = inv                  # pid per append
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

        self._global_spines()

    def _pid_of(self, keys, vals) -> np.ndarray:
        """pid per (key, val); -1 only for pairs seen neither in an
        append nor in any read (writerless pairs have a pid with
        w_txn[pid] == -1)."""
        codes = _pack(np.asarray(keys, dtype=np.int64),
                      np.asarray(vals, dtype=np.int64))
        if len(self.pair_codes) == 0:
            return np.full(len(codes), -1, dtype=np.int64)
        pos = np.searchsorted(self.pair_codes, codes)
        pos = np.clip(pos, 0, len(self.pair_codes) - 1)
        return np.where(self.pair_codes[pos] == codes, pos, -1)


    def _global_spines(self):
        f = self.flat
        R = len(f.rd_txn)
        K = len(f.key_names)
        # spine read per key: longest, earliest on ties (host tie-break)
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
        # flat spine arrays
        srd = self.spine_read[self.spine_read >= 0]
        skey = np.flatnonzero(self.spine_read >= 0)
        self.sp_key_of = skey
        lens = f.rd_len[srd] if srd.size else np.empty(0, dtype=np.int64)
        self.sp_off = np.zeros(K, dtype=np.int64)
        off = np.concatenate([[0], np.cumsum(lens)])[:-1] \
            if srd.size else np.empty(0, dtype=np.int64)
        self.sp_off[skey] = off
        # gather spine element values
        idx = []
        for r in srd:
            idx.append(np.arange(f.rd_off[r], f.rd_off[r] + f.rd_len[r]))
        self.sp_vals = (f.re_vals[np.concatenate(idx)] if idx
                        else np.empty(0, dtype=np.int64))
        self.sp_keys = np.repeat(skey, lens) if srd.size else \
            np.empty(0, dtype=np.int64)
        self.sp_pid = self._pid_of(self.sp_keys, self.sp_vals)
        # successor pid along each spine
        P = len(self.pair_codes)
        self.pair_nxt = np.full(P, -1, dtype=np.int64)
        if len(self.sp_pid) > 1:
            same = self.sp_keys[1:] == self.sp_keys[:-1]
            a = self.sp_pid[:-1][same]
            b = self.sp_pid[1:][same]
            good = a >= 0
            self.pair_nxt[a[good]] = b[good]
        # incompatible-order: each read must be a prefix of its spine
        if R:
            too_long = f.rd_len > self.spine_len[f.rd_key]
            elem_pos = np.arange(len(f.re_vals)) - f.rd_off[f.re_read]
            sp_at = self.sp_off[f.rd_key[f.re_read]] + elem_pos
            in_range = elem_pos < self.spine_len[f.rd_key[f.re_read]]
            if len(self.sp_vals):
                sp_val = np.where(in_range, self.sp_vals[
                    np.clip(sp_at, 0, len(self.sp_vals) - 1)], -1)
            else:
                sp_val = np.full(len(f.re_vals), -1, dtype=np.int64)
            mismatch = np.where(in_range, sp_val != f.re_vals, True)
            bad = too_long.copy()
            np.logical_or.at(bad, f.re_read, mismatch)
            for r in np.flatnonzero(bad):
                o, n_ = int(f.rd_off[r]), int(f.rd_len[r])
                k = int(f.rd_key[r])
                so, sl = int(self.sp_off[k]), int(self.spine_len[k])
                self.anomalies["incompatible-order"].append({
                    "key": f.key_names[k],
                    "read": f.re_vals[o:o + n_].tolist(),
                    "spine": self.sp_vals[so:so + sl].tolist(),
                    "op": self._op(f.rd_txn[r])})

    def _read_anomalies(self):
        f = self.flat
        re_pid = self._pid_of(f.rd_key[f.re_read], f.re_vals)
        self.re_pid = re_pid
        # every read element has a pid now; writerless pairs carry -1
        re_w = np.where(re_pid >= 0,
                        self.w_txn[np.clip(re_pid, 0, None)]
                        if len(self.w_txn) else -1, -1)
        unobs = re_w < 0
        for i in np.flatnonzero(unobs):
            r = f.re_read[i]
            self.anomalies["unobservable-read"].append({
                "key": f.key_names[f.rd_key[r]],
                "value": int(f.re_vals[i]), "op": self._op(f.rd_txn[r])})
        aborted = np.zeros(len(re_pid), dtype=bool)
        if len(self.w_txn):
            aborted[~unobs] = self.w_fail[re_pid[~unobs]]
        for i in np.flatnonzero(aborted):
            r = f.re_read[i]
            self.anomalies["G1a"].append({
                "key": f.key_names[f.rd_key[r]],
                "value": int(f.re_vals[i]), "op": self._op(f.rd_txn[r]),
                "writer": self._op(self.w_txn[re_pid[i]])})
        # G1b: last element is an intermediate version of another txn
        nz = np.flatnonzero(f.rd_len > 0)
        last_idx = f.rd_off[nz] + f.rd_len[nz] - 1
        last_pid = re_pid[last_idx]
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


_CASES = ([f"fixture-{n}" for n in sorted(FIXTURES)]
          + [f"valid-{s}" for s in range(3)]
          + [f"planted-{s}" for s in range(8)])


def _case_history(case):
    kind, name = case.split("-", 1)
    if kind == "fixture":
        return FIXTURES[name]
    if kind == "valid":
        return History(gen_history(random.Random(int(name)), 200))
    return planted_history(int(name))


def _no_native(*_args):
    raise RuntimeError("native prefix pass unavailable")


@pytest.mark.parametrize("prefix", ["native", "numpy"])
@pytest.mark.parametrize("case", _CASES)
def test_spine_positions_match_global_sort(case, prefix, monkeypatch):
    """Reading each element off its key's spine position gives the
    global sort's pids, version orders, anomalies (in order), edges and
    serialization, through the C prefix pass and its numpy fallback."""
    if prefix == "numpy":
        monkeypatch.setattr(native, "off_spine", _no_native)
    hist = _case_history(case)
    new = elle_device.DeviceAppendAnalysis(hist, device=False)
    ref = GlobalSortAnalysis(hist, device=False)
    for name in ("pair_codes", "ap_pid", "sp_pid", "pair_nxt", "nz_reads",
                 "last_pid", "edge_src", "edge_dst", "edge_ty",
                 "edge_key"):
        np.testing.assert_array_equal(getattr(new, name),
                                      getattr(ref, name), err_msg=name)
    assert list(new.anomalies.items()) == list(ref.anomalies.items())
    s_new, s_ref = new.serialization(), ref.serialization()
    assert (s_new is None) == (s_ref is None)
    if s_ref is not None:
        np.testing.assert_array_equal(s_new, s_ref)
    if case.startswith("planted"):
        f = new.flat
        assert {"incompatible-order", "unobservable-read", "G1a", "G1b",
                "duplicate-appends", "internal"} <= set(new.anomalies)
        # off the spine both mid-read and at a read's last element
        r = new.off_read
        last = new.off_elem == f.rd_off[r] + f.rd_len[r] - 1
        assert last.any() and (~last).any()
        # writerless and failed-writer values read by more than one
        # read: inside prefixes, not only where a read leaves its spine
        for kind in ("unobservable-read", "G1a"):
            recs = new.anomalies[kind]
            assert len({(x["key"], x["value"]) for x in recs}) < len(recs)
        assert (f.rd_len == 0).any()
        assert (new.spine_read < 0).any()  # keys no read observed



@pytest.mark.skipif(native.jlog() is None, reason="no C toolchain")
@pytest.mark.parametrize("seed,reads", [(0, 400), (1, 400), (2, 1), (3, 0)])
def test_c_prefix_pass_equals_python(seed, reads):
    """order.c's jt_off_spine is _off_spine_py: reads that are prefixes
    of their key's spine, some empty, some keys with no spine, and a few
    elements changed."""
    rng = np.random.default_rng(seed)
    sp_len = rng.integers(0, 12, 50)
    sp_len[:3] = 0
    sp_off = np.cumsum(sp_len) - sp_len
    sp_vals = rng.integers(0, 1 << 40, int(sp_len.sum()))
    rd_key = rng.integers(0, 50, reads)
    rd_len = (rng.random(reads) * (sp_len[rd_key] + 1)).astype(np.int64)
    re_vals = np.concatenate(
        [np.empty(0, dtype=np.int64)]
        + [sp_vals[o:o + n] for o, n in zip(sp_off[rd_key], rd_len)])
    flip = rng.random(len(re_vals)) < 0.05
    re_vals[flip] += 1
    args = (rd_key, rd_len, sp_off, sp_vals, re_vals)
    c = native.off_spine(*args)
    assert c.tolist() == elle_device._off_spine_py(*args).tolist()
    assert c.tolist() == np.flatnonzero(flip).tolist()

def test_random_differential():
    rng = random.Random(11)
    for trial in range(25):
        ops = gen_history(rng, rng.choice([20, 60, 150]))
        if trial % 2 == 1:
            ops = corrupt(rng, ops)
        h = History(ops)
        rh = elle.check_list_append(h, {"engine": "host"})
        rd = elle_device.check_list_append_device(h)
        assert rh["valid?"] == rd["valid?"], (trial, rh, rd)
        assert rh["anomaly-types"] == rd["anomaly-types"], (trial, rh, rd)


def test_auto_engine_dispatch():
    """auto uses device for big histories, host for small; both agree;
    non-internable values fall back to host silently."""
    rng = random.Random(2)
    ops = gen_history(rng, 40)
    small = elle.check_list_append(History(ops))
    assert small["valid?"] is True
    weird = T(("invoke", 0, [["append", "x", "not-an-int"]]),
              ("ok", 0, [["append", "x", "not-an-int"]]),
              ("invoke", 1, [["r", "x", None]]),
              ("ok", 1, [["r", "x", ["not-an-int"]]]))
    res = elle.check_list_append(weird, {"engine": "auto"})
    assert res["valid?"] is True
    with pytest.raises(elle_device.Unvectorizable):
        elle.check_list_append(weird, {"engine": "device"})


def test_scc_kernel_matches_host_random():
    rng = np.random.default_rng(5)
    prev = scc_mod.DEVICE_MIN_EDGES
    scc_mod.DEVICE_MIN_EDGES = 1  # force the device path at test sizes
    try:
        for _ in range(15):
            n = 150
            e = rng.integers(0, n, size=(300, 2))
            d = scc_mod.scc(n, e[:, 0], e[:, 1], device=True)
            h = scc_mod._scc_host(n, e[:, 0], e[:, 1])
            assert (d == h).all()
    finally:
        scc_mod.DEVICE_MIN_EDGES = prev


def test_scc_edge_mask_subsets():
    src = np.array([0, 1, 2, 3])
    dst = np.array([1, 0, 3, 2])
    mask1 = np.array([True, True, False, False])
    labels = scc_mod.scc(4, src, dst, emask=mask1, device=False)
    groups = scc_mod.nontrivial_from_labels(labels)
    assert [sorted(g.tolist()) for g in groups] == [[0, 1]]


def test_scc_adversarial_chain_falls_back():
    """A decreasing chain exceeds the sweep cap on device; the host
    fallback must still give exact singleton labels."""
    n = 3000
    src = np.arange(n - 1, 0, -1)
    dst = np.arange(n - 2, -1, -1)
    labels = scc_mod.scc(n, src, dst, device=True)
    assert (labels == np.arange(n)).all()


def test_unobservable_last_element_still_gets_rw():
    """The anti-dependency is keyed by raw value (host nxt dict), so it
    must fire even when the read's last element has no writer
    (round-3 review finding: the pid-based lookup dropped the edge)."""
    hist = T(
        ("invoke", 0, [["append", "x", 1]]), ("ok", 0, [["append", "x", 1]]),
        ("invoke", 1, [["append", "x", 2]]), ("ok", 1, [["append", "x", 2]]),
        ("invoke", 2, [["r", "x", None]]),
        ("ok", 2, [["r", "x", [1, 999, 2]]]),   # 999 never appended
        ("invoke", 3, [["r", "x", None]]),
        ("ok", 3, [["r", "x", [1, 999]]]))
    rh = elle.check_list_append(hist, {"engine": "host"})
    rd = elle_device.check_list_append_device(hist)
    assert rh["valid?"] == rd["valid?"]
    assert rh["anomaly-types"] == rd["anomaly-types"], (rh, rd)


class TestRwRegisterDeviceDispatch:
    """check_rw_register's device-SCC dispatch must agree with the
    host cycle search (BASELINE config 3 covers rw-register too)."""

    def test_engines_agree_on_fixtures(self):
        cases = [
            # valid
            T(("invoke", 0, [["w", "x", 1]]), ("ok", 0, [["w", "x", 1]]),
              ("invoke", 1, [["r", "x", None]]), ("ok", 1, [["r", "x", 1]])),
            # wr cycle (G1c)
            T(("invoke", 0, [["w", "x", 1], ["r", "y", None]]),
              ("invoke", 1, [["w", "y", 2], ["r", "x", None]]),
              ("ok", 0, [["w", "x", 1], ["r", "y", 2]]),
              ("ok", 1, [["w", "y", 2], ["r", "x", 1]])),
        ]
        for hist in cases:
            rd = elle.check_rw_register(hist, {"engine": "device"})
            rh = elle.check_rw_register(hist, {"engine": "host"})
            assert rd["valid?"] == rh["valid?"]
            assert rd["anomaly-types"] == rh["anomaly-types"]

    def test_engines_agree_on_generated(self):
        from jepsen_tpu.tpu import synth

        hist = synth.rw_register_history(2000, seed=9)
        rd = elle.check_rw_register(hist, {"engine": "device"})
        rh = elle.check_rw_register(hist, {"engine": "host"})
        assert rd["valid?"] is rh["valid?"] is True


def test_rw_none_first_read_not_promoted_to_external():
    """A None first read is the key's external read (txn.clj ext-reads
    semantics): a later valued read of the same key must not emit the
    rw edge the host engine never produces (review r3)."""
    hist = T(
        ("invoke", 0, [["w", "x", 1]]), ("ok", 0, [["w", "x", 1]]),
        ("invoke", 1, [["w", "x", 2]]), ("ok", 1, [["w", "x", 2]]),
        # reads None first, then 1, in one txn; succ[(x,1)]=2 exists
        # via t2's write-follows-read
        ("invoke", 2, [["r", "x", None], ["r", "x", None]]),
        ("ok", 2, [["r", "x", None], ["r", "x", 1]]),
        ("invoke", 3, [["r", "x", None], ["w", "x", 3]]),
        ("ok", 3, [["r", "x", 1], ["w", "x", 3]]))
    rh = elle.check_rw_register(hist, {"engine": "host"})
    rd = elle.check_rw_register(hist, {"engine": "device"})
    assert rd["valid?"] == rh["valid?"]
    assert rd["anomaly-types"] == rh["anomaly-types"]
    assert rd["edge-count"] == rh["edge-count"]


def test_rw_unvectorizable_values_still_check():
    """String register values can't intern; engine=device must fall
    back to host inference + device SCC and agree with host."""
    hist = T(
        ("invoke", 0, [["w", "x", "a"]]),
        ("ok", 0, [["w", "x", "a"]]),
        ("invoke", 1, [["r", "x", None]]),
        ("ok", 1, [["r", "x", "a"]]))
    rd = elle.check_rw_register(hist, {"engine": "device"})
    rh = elle.check_rw_register(hist, {"engine": "host"})
    assert rd["valid?"] is rh["valid?"] is True
