"""List-append histories for the elle-list-append cells: a base history
from a simulated strict-serializable store at a fixed history seed, its
variants drawn from --seed, the program's History, and the comparison
of the program's verdicts and certificates with the plain reference.

Events are tuples (type, process, "txn", micro-ops) with the op's index
equal to its position; micro-ops are ["append", k, v] and ["r", k, list]
(the invocation's reads carry None), as jepsen.tests.cycle.append's
clients record them. Nothing here imports the program except
`registers.program_history`, which builds its History.
"""

from __future__ import annotations

import contextlib
import gc
import random

from . import registers

# a prime above every key, value mask and process id the bijections meet
_M = (1 << 31) - 1


def _txn(rng, p: dict, next_val: dict, first: list) -> list:
    """One transaction of elle.list-append/gen (cycle.append_checker's
    generator): 1-4 micro-ops over `key_count` live keys, each a read
    with probability 0.5, else an append of the key's next value; a key
    is retired once it has `max_writes_per_key` appends."""
    mw = p["max_writes_per_key"]
    while next_val.get(first[0], 0) >= mw:
        first[0] += 1
    keys = range(first[0], first[0] + p["key_count"])
    txn = []
    for _ in range(rng.randint(p["min_txn_length"], p["max_txn_length"])):
        k = rng.choice(keys)
        if rng.random() < 0.5 or next_val.get(k, 0) >= mw:
            txn.append(["r", k, None])
        else:
            v = next_val.get(k, 0) + 1
            next_val[k] = v
            txn.append(["append", k, v])
    return txn


@contextlib.contextmanager
def _no_collection():
    """The collector off while the benchmark builds its own millions of
    lists (it would rescan the bases each time); the program runs with
    it on."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def base(p: dict, history_seed: int) -> tuple[list, list]:
    with _no_collection():
        return _base(p, history_seed)


def _base(p: dict, history_seed: int) -> tuple[list, list]:
    """(events, witness) of a simulated strict-serializable store at
    `history_seed`. `concurrency` client threads each keep one
    transaction in flight; a transaction takes effect at one instant
    between its invocation and its completion, and its reads return the
    key's list at that instant. A transaction aborts (:fail, no effect)
    with probability `fail_p`; with probability `info_p` its outcome is
    indeterminate (:info) and it took effect or not, drawn from the
    seed, after which its thread takes a new process id (+concurrency),
    as Jepsen's interpreter does. The witness is the order in which
    transactions took effect, by invocation position."""
    rng = random.Random(history_seed)
    conc = p["concurrency"]
    fail_p, info_p = p["fail_p"], p["info_p"]
    lists: dict = {}
    next_val: dict = {}
    first = [0]
    proc = list(range(conc))
    events: list = []
    witness: list = []
    append = events.append
    # thread -> [txn, took effect?, outcome, completion mops, inv pos]
    open_: dict = {}
    budget = p["n_txns"]
    while budget > 0 or open_:
        unapplied = [t for t, o in open_.items() if o[2] is None]
        applied = [t for t, o in open_.items() if o[2] is not None]
        r = rng.random()
        if budget > 0 and len(open_) < conc and (r < 0.45 or not open_):
            t = rng.choice([q for q in range(conc) if q not in open_])
            txn = _txn(rng, p, next_val, first)
            open_[t] = [txn, False, None, None, len(events)]
            append(("invoke", proc[t], "txn", txn))
            budget -= 1
        elif unapplied and (r < 0.75 or not applied):
            o = open_[rng.choice(unapplied)]
            x = rng.random()
            if x < fail_p:
                o[2] = "fail"
                continue
            o[2] = "ok" if x >= fail_p + info_p else "info"
            if o[2] == "info" and rng.random() < 0.5:
                continue  # an indeterminate txn that never took effect
            done = []
            for f, k, v in o[0]:
                cur = lists.get(k)
                if cur is None:
                    cur = lists[k] = []
                if f == "append":
                    cur.append(v)
                    done.append([f, k, v])
                else:
                    done.append([f, k, list(cur)])
            o[1], o[3] = True, done
            witness.append(o[4])
        elif applied:
            t = rng.choice(applied)
            txn, _took, outcome, done, _pos = open_.pop(t)
            if outcome == "ok":
                append(("ok", proc[t], "txn", done))
            else:
                append((outcome, proc[t], "txn", txn))
                if outcome == "info":
                    proc[t] += conc
    return events, witness


def variant(p: dict, events: list, seed: int, key) -> list:
    """The base history with its keys renamed by a bijection drawn from
    (seed, key), each key's values by another and the process ids by a
    third. The variant keeps the event order exactly: it does not call
    synth.reorder_completions. So the dependency graph is the base's,
    node for node (the SCC kernel numbers transactions in history order,
    and its colouring's sweeps follow that numbering), and the SCC runs
    the same sweeps and rounds on every seed, while the history's
    content differs on every seed, so no answer keyed on the history can
    be replayed."""
    rng = random.Random(f"{seed}/variant/{key}")
    with _no_collection():
        return _variant(events, rng)


def _variant(events: list, rng) -> list:
    ka, kb, ma, mb, pa, pb = (rng.randrange(1, _M) for _ in range(6))
    keys: dict = {}  # key -> (its new name, the mask of its values)
    out = []
    for t, pr, f, v in events:
        mops = []
        for op, k, x in v:
            kv = keys.get(k)
            if kv is None:
                kv = keys[k] = ((ka * k + kb) % _M, (ma * k + mb) % _M)
            if x is None:
                mops.append([op, kv[0], None])
            elif op == "append":
                mops.append([op, kv[0], x ^ kv[1]])
            else:
                mops.append([op, kv[0], [y ^ kv[1] for y in x]])
        out.append((t, (pa * pr + pb) % _M, f, mops))
    return out


def program_input(data):
    """The program's input for one verdict; data is (events, witness)."""
    return registers.program_history(data[0])


def compare(cell, items) -> None:
    """items: (events, witness, program result) per verdict. The
    reference decides each verdict by proof (the generator's witness
    replayed; its own full search where it does not replay) and replays
    each certificate. Records two exact checks on the cell."""
    ref = cell.reference
    mismatch = cert_faults = 0
    for i, (events, witness, res) in enumerate(items):
        txns = ref.transactions(events)
        want = ref.verdict(events, witness, txns=txns)
        if res is None or res.get("valid?") is not want:
            mismatch += 1
            got = None if res is None else res.get("valid?")
            cell.log(f"verdict {i}: program {got!r}, reference {want!r}")
        why = ref.replay(events, res or {}, txns=txns)
        if why is not None:
            cert_faults += 1
            if cert_faults <= 3:
                cell.log(f"certificate {i}: {why}")
    cell.log(f"compared {len(items)} verdicts with the reference")
    cell.check("verdict_mismatches", mismatch, 0)
    cell.check("certificate_faults", cert_faults, 0)
