"""Plain reference for the elle-list-append deployment: strict
serializability of a list-append history, by Elle's inference (Kingsbury
and Alvaro, "Elle", VLDB 2020) and Adya's anomaly classes.

It imports nothing of the program. `verdict` decides by proof where it
can: the generator's commit order, replayed under list-append semantics
with process and realtime order kept, proves a history valid. Where that
order does not replay, `search` runs Elle's analysis on its own: version
orders from the longest committed read of each key (a read that is not a
prefix of it is an incompatible order), aborted (G1a) and intermediate
(G1b) reads, internal consistency, duplicate appends, then the ww, wr and
rw dependency edges, the process and realtime orders, and an iterative
Tarjan SCC. The history is valid iff none of these finds an anomaly and
the graph has no component of two or more transactions.

`replay` checks the certificate the program attached: a valid verdict's
serialization must replay (every committed read returns exactly the
appends before it, process and realtime order kept); each edge of an
invalid verdict's cycle must be an edge of this module's own graph, of
the same type. `classify` names a cycle by Adya's and Elle's classes.

Events are tuples (type, process, "txn", micro-ops) with the op's index
equal to its position.
"""

from __future__ import annotations

BIG = 1 << 62


class Txn:
    __slots__ = ("inv", "ret", "type", "process", "mops")

    def __init__(self, inv, ret, type_, process, mops):
        self.inv = inv
        self.ret = ret
        self.type = type_
        self.process = process
        self.mops = mops


def transactions(events) -> dict:
    """invocation position -> Txn. A committed txn carries its
    completion's micro-ops (with the reads' values), any other its
    invocation's; an invocation never completed is indeterminate."""
    out: dict = {}
    open_: dict = {}
    for pos, (typ, proc, _f, value) in enumerate(events):
        if typ == "invoke":
            open_[proc] = (pos, value)
        elif proc in open_:
            inv, mops = open_.pop(proc)
            out[inv] = Txn(inv, pos, typ, proc,
                           value if typ == "ok" else mops)
    for proc, (inv, mops) in open_.items():
        out[inv] = Txn(inv, BIG, "info", proc, mops)
    return out


def _hk(k):
    return k if isinstance(k, (int, str)) else repr(k)


def replay_order(txns: dict, order) -> str | None:
    """None when `order` (invocation positions) serializes the history:
    every committed txn once, no aborted one, each committed read equal
    to its key's list of appends so far, no txn before one that
    completed ahead of its invocation (an indeterminate txn's completion
    is unknown), each process's committed txns in their own order (an
    indeterminate txn may take effect after its process moved on). Else
    why not."""
    if len(set(order)) != len(order):
        return "a txn appears twice"
    n_ok = 0
    for i in order:
        t = txns.get(i)
        if t is None or t.type == "fail":
            return f"entry {i!r} is neither committed nor indeterminate"
        n_ok += t.type == "ok"
    if n_ok != sum(1 for t in txns.values() if t.type == "ok"):
        return "a committed txn is missing"
    lists: dict = {}
    max_inv = -1
    last: dict = {}
    for i in order:
        t = txns[i]
        if t.type == "ok" and t.ret < max_inv:
            return f"txn {i} is ordered after a txn invoked after it completed"
        max_inv = max(max_inv, t.inv)
        if t.type == "ok":
            if last.get(t.process, -1) > t.inv:
                return (f"txn {i} is ordered before an earlier txn of its "
                        "process")
            last[t.process] = t.inv
        for f, k, v in t.mops:
            cur = lists.setdefault(_hk(k), [])
            if f == "append":
                cur.append(v)
            elif f == "r" and t.type == "ok" and v is not None \
                    and list(v) != cur:
                return (f"txn {i} read {list(v)!r} of key {k!r}, the "
                        f"order gives {cur!r}")
    return None


# -- Elle's inference -------------------------------------------------------

def search(txns: dict) -> dict:
    """Elle's analysis: {"anomalies": {class: count}, "edges": {(a, b):
    set of types}, "sccs": [[txn, ...]]}, txns by invocation position."""
    anomalies: dict = {}

    def found(name):
        anomalies[name] = anomalies.get(name, 0) + 1

    writer: dict = {}     # (key, value) -> (txn, index among its appends
    appends: dict = {}    # to the key, its appends to the key)
    writers_of: dict = {}  # key -> non-aborted writer txns
    # txns in completion order: which read of a key's longest length is
    # its version order, and which of two duplicate appends is its
    # writer, follow Elle's order of the history's completions
    by_ret = sorted(txns, key=lambda i: txns[i].ret)
    for i in by_ret:
        t = txns[i]
        mine: dict = {}
        for f, k, v in t.mops:
            if f == "append":
                mine.setdefault(_hk(k), []).append(v)
        for k, vs in mine.items():
            if t.type != "fail":
                writers_of.setdefault(k, []).append(i)
            for j, v in enumerate(vs):
                prev = writer.get((k, v))
                if prev is not None and t.type != "fail" \
                        and txns[prev[0]].type != "fail":
                    found("duplicate-appends")
                if t.type != "fail" or prev is None:
                    writer[(k, v)] = (i, j, len(vs))
        appends[i] = mine
    reads = [(i, _hk(k), list(v)) for i in by_ret
             if txns[i].type == "ok"
             for f, k, v in txns[i].mops if f == "r" and v is not None]
    spine: dict = {}
    for _i, k, vs in reads:
        if len(vs) > len(spine.get(k, [])):
            spine[k] = vs
    for i, k, vs in reads:
        if vs != spine.get(k, [])[:len(vs)]:
            found("incompatible-order")
        for v in vs:
            w = writer.get((k, v))
            if w is None:
                found("unobservable-read")
            elif txns[w[0]].type == "fail":
                found("G1a")
        if vs:
            w = writer.get((k, vs[-1]))
            if w is not None and w[1] != w[2] - 1 and w[0] != i:
                found("G1b")
    for i in by_ret:
        t = txns[i]
        if t.type != "ok":
            continue
        own: dict = {}
        for f, k, v in t.mops:
            k = _hk(k)
            if f == "append":
                own.setdefault(k, []).append(v)
            elif f == "r" and v is not None and own.get(k):
                mine = own[k]
                if list(v)[-len(mine):] != mine:
                    found("internal")
                    break

    edges: dict = {}

    def edge(a, b, ty):
        if a != b:
            edges.setdefault((a, b), set()).add(ty)

    def live(k, v):
        w = writer.get((k, v))
        return None if w is None or txns[w[0]].type == "fail" else w[0]

    for k, sp in spine.items():
        prev = None
        for v in sp:
            w = live(k, v)
            if w is None:
                continue
            if prev is not None:
                edge(prev, w, "ww")
            prev = w
    after: dict = {}  # (key, value) -> the next value of its version order
    for k, sp in spine.items():
        for u, v in zip(sp, sp[1:]):
            after.setdefault((k, u), v)
    for i, k, vs in reads:
        sp = spine.get(k, [])
        if vs:
            w = live(k, vs[-1])
            if w is not None:
                edge(w, i, "wr")
            if (k, vs[-1]) in after:
                w = live(k, after[(k, vs[-1])])
                if w is not None:
                    edge(i, w, "rw")
        else:
            on_spine = {live(k, v) for v in sp}
            targets = set(writers_of.get(k, [])) - on_spine
            first = next((w for w in (live(k, v) for v in sp)
                          if w is not None), None)
            if first is not None:
                targets.add(first)
            for w in targets:
                edge(i, w, "rw")
    committed = sorted((i for i, t in txns.items() if t.type == "ok"),
                       key=lambda i: txns[i].inv)
    last: dict = {}
    for i in committed:
        p = txns[i].process
        if p in last:
            edge(last[p], i, "process")
        last[p] = i
    for a, b in realtime_edges(txns, committed):
        edge(a, b, "realtime")
    return {"anomalies": anomalies, "edges": edges,
            "sccs": [c for c in tarjan(edges) if len(c) > 1]}


def realtime_edges(txns: dict, committed) -> list:
    """A -> B for committed A, B with A completed before B invoked,
    reduced to the edges that imply the rest: at B's invocation, from
    each completed txn that no other completed txn follows in real
    time."""
    marks = sorted([(txns[i].inv, 1, i) for i in committed]
                   + [(txns[i].ret, 0, i) for i in committed])
    frontier: list = []
    out = []
    for _pos, is_inv, i in marks:
        if is_inv:
            out.extend((a, i) for a in frontier)
        else:
            frontier = [a for a in frontier if txns[a].ret > txns[i].inv]
            frontier.append(i)
    return out


def tarjan(edges: dict) -> list:
    """Strongly connected components of the graph, iteratively."""
    adj: dict = {}
    for a, b in edges:
        adj.setdefault(a, []).append(b)
        adj.setdefault(b, [])
    index: dict = {}
    low: dict = {}
    on: set = set()
    stack: list = []
    out = []
    for root in adj:
        if root in index:
            continue
        work = [(root, 0)]
        while work:
            v, j = work.pop()
            if j == 0:
                index[v] = low[v] = len(index)
                stack.append(v)
                on.add(v)
            if j < len(adj[v]):
                work.append((v, j + 1))
                w = adj[v][j]
                if w not in index:
                    work.append((w, 0))
                elif w in on:
                    low[v] = min(low[v], index[w])
                continue
            if low[v] == index[v]:
                comp = []
                while True:
                    w = stack.pop()
                    on.discard(w)
                    comp.append(w)
                    if w == v:
                        break
                out.append(comp)
            if work:
                u = work[-1][0]
                low[u] = min(low[u], low[v])
    return out


def classify(cycle) -> str:
    """Adya's class of a dependency cycle, as Elle names it: G0 (write
    cycles), G1c (ww and wr), G-single (one anti-dependency), G2-item
    (two or more); -realtime where a realtime edge closes it, else
    -process where a process edge does. `cycle`: (from, to, type)."""
    types = [ty for _a, _b, ty in cycle]
    n_rw = types.count("rw")
    if n_rw == 0:
        name = "G1c" if "wr" in types else "G0"
    else:
        name = "G-single" if n_rw == 1 else "G2-item"
    if "realtime" in types:
        name += "-realtime"
    elif "process" in types:
        name += "-process"
    return name


# -- the comparison ---------------------------------------------------------

def verdict(events, witness=None, txns: dict | None = None) -> bool:
    """True when the history is strict serializable by Elle's inference:
    by proof where the generator's order replays, else by search."""
    txns = transactions(events) if txns is None else txns
    if witness is not None and replay_order(txns, witness) is None:
        return True
    s = search(txns)
    return not s["anomalies"] and not s["sccs"]


def replay(events, result: dict, txns: dict | None = None) -> str | None:
    """None when the result's certificate proves its verdict, else
    why not."""
    cert = result.get("certificate")
    if not isinstance(cert, dict):
        return "no certificate"
    txns = transactions(events) if txns is None else txns
    if result.get("valid?") is True:
        order = cert.get("serialization")
        if not isinstance(order, list):
            return "a valid verdict without a serialization"
        return replay_order(txns, order)
    cycle = cert.get("cycle")
    if not cycle:
        return None  # a non-cycle anomaly, or an honest absence
    return cycle_fault(txns, [(e.get("from"), e.get("to"), e.get("type"))
                              for e in cycle])


def cycle_fault(txns: dict, cycle, edges: dict | None = None) -> str | None:
    """None when each (from, to, type) of `cycle` chains into the next
    and is an edge of this module's graph of that type (process and
    realtime edges as relations: same process and invoked earlier;
    completed before the other's invocation)."""
    if edges is None:
        edges = search(txns)["edges"]
    for j, (a, b, ty) in enumerate(cycle):
        if b != cycle[(j + 1) % len(cycle)][0]:
            return f"edge {j} does not chain into the next"
        ta, tb = txns.get(a), txns.get(b)
        if ta is None or tb is None:
            return f"edge {j} names no txn"
        if ty == "realtime":
            ok = ta.ret < tb.inv
        elif ty == "process":
            ok = ta.process == tb.process and ta.inv < tb.inv
        else:
            ok = ty in edges.get((a, b), ())
        if not ok:
            return f"edge {j} ({a} -{ty}-> {b}) is not in the graph"
    return None
