"""One stale read planted in a crash-free register history: the anomaly
of the lin-register-1m-anomaly85 cell.

The plant makes a history that no linearization explains, whatever the
order of the ops that overlap. It takes the first ok read r invoked at
or after a share `at` of the events for which the span from the
invocation of w to the completion of r overlaps no write or cas but w,
w being the last write or cas completed ok before r's invocation. Every
linearization then places w before r and no write or cas between them,
so r must return w's value. The plant has r return the value the
register held before w took effect (the generator's witness says
which), where that value differs from w's and is not nil (a nil read is
an unknown read, as Knossos reads it). The history's event order is
unchanged, so the variants of a planted base (`registers.variant`:
values renamed, adjacent completions reordered) keep it stale.
"""

from __future__ import annotations

import bisect


def _ops(events):
    """inv pos -> [f, inv value, completion type, completion pos]."""
    ops: dict = {}
    open_: dict = {}
    for i, (t, p, f, v) in enumerate(events):
        if t == "invoke":
            open_[p] = i
            ops[i] = [f, v, None, None]
        else:
            j = open_.pop(p)
            ops[j][2], ops[j][3] = t, i
    return ops


def plant(events: list, witness: list, at: float) -> tuple[list, int]:
    """(the history with one stale read, the read's completion pos)."""
    ops = _ops(events)
    if any(o[2] not in ("ok", "fail") for o in ops.values()):
        raise ValueError("the plant needs a crash-free history")
    before: dict = {}       # inv pos of a write/ok cas -> value before it
    state = None
    for i in witness:
        f, v = ops[i][0], ops[i][1]
        if f == "write":
            before[i], state = state, v
        elif f == "cas":
            before[i], state = state, v[1]
    changes = sorted((o[3], i) for i, o in ops.items()
                     if o[0] in ("write", "cas") and o[2] == "ok")
    # write/cas intervals by invocation, to find what overlaps a span
    wc = sorted((i, o[3]) for i, o in ops.items()
                if o[0] in ("write", "cas"))
    starts = [s for s, _e in wc]
    longest = max((e - s for s, e in wc), default=0)
    start = int(at * len(events))
    c = 0
    for i in sorted(ops):
        f, _v, t, ret = ops[i]
        if i < start or f != "read" or t != "ok":
            continue
        while c < len(changes) and changes[c][0] < i:
            c += 1
        if not c:
            continue
        w = changes[c - 1][1]
        wf, wv = ops[w][0], ops[w][1]
        new = wv if wf == "write" else wv[1]
        stale = before[w]
        if stale is None or stale == new:
            continue
        lo = bisect.bisect_left(starts, w - longest)
        hi = bisect.bisect_right(starts, ret)
        if any(s != w and e >= w for s, e in wc[lo:hi]):
            continue
        out = list(events)
        tt, p, rf, _rv = out[ret]
        out[ret] = (tt, p, rf, stale)
        return out, ret
    raise ValueError("no read to plant a stale value on")
