"""Independent-key register histories for the independent-cas-register
cells: a base history from a simulated linearizable key-value store
under a partition nemesis at a fixed history seed, its variants drawn
from --seed, and the key-by-key comparison of the program's verdicts
and certificates with the plain reference.

The store is the benchmark's own, as `synth.py` and `appends.py` are.
Its clients follow jepsen.tests.linearizable-register: an
`independent/concurrent-generator` of 2n threads a key group, n of them
reserved for reads and the others drawing from [w cas cas], each key's
generator wrapped in `gen/limit` (the per-key limit times a factor drawn
from [0.9, 1.0), as the source draws it) inside `gen/process-limit 20`.

Events are tuples (type, process, f, (key, value)), the op's index its
position; a read's invocation carries None, a cas [expected, new].
Nothing here imports the program; `registers.program_input` builds its
History.
"""

from __future__ import annotations

import itertools
import random

# a prime above every key and process id the bijections meet
_M = (1 << 31) - 1
_PERMS = list(itertools.permutations(range(5)))


class _Group:
    """One thread group's key: its name, the ops its generator has
    emitted and may emit, and the processes it has seen (ProcessLimit's
    set: the union of the group's processes at each emission)."""

    __slots__ = ("key", "emitted", "limit", "procs")

    def __init__(self, key, limit, procs):
        self.key = key
        self.emitted = 0
        self.limit = limit
        self.procs = set(procs)


def base(p: dict, history_seed: int) -> tuple[list, list]:
    """(events, witness) of the simulated store at `history_seed`.

    `concurrency` client threads in groups of `group_size`; the first
    `readers` threads of a group only read, the others write or cas at
    1:2 over values [0, n_values). Each thread keeps one op in flight;
    an op takes effect at one instant between its invocation and its
    completion. The nemesis partitions and heals in alternate spans of
    `nemesis_ops` invocations of the interleaved history, healed first;
    an op's outcome is indeterminate (:info) with probability
    `info_p_partitioned` or `info_p_healed` as the span it takes effect
    in is, and such an op took effect with probability
    `info_effect_p`; its thread then takes a new process id
    (+concurrency), as Jepsen's interpreter does. A group's key ends
    once its generator has emitted its limit, or when an emission would
    make its process set exceed `process_limit`; the group then takes
    the next key. The witness is the order in which ops took effect, by
    invocation position."""
    rng = random.Random(history_seed)
    conc, gsize, readers = p["concurrency"], p["group_size"], p["readers"]
    nval, plim = p["n_values"], p["process_limit"]
    per_key, span = p["per_key_limit"], p["nemesis_ops"]
    info_p = (p["info_p_healed"], p["info_p_partitioned"])
    effect_p = p["info_effect_p"]
    proc = list(range(conc))
    values: dict = {}        # key -> the register's value
    next_key = [0]

    def fresh(g):
        k = next_key[0]
        next_key[0] += 1
        limit = int((0.9 + 0.1 * rng.random()) * per_key + 0.999999)
        return _Group(k, limit, proc[g * gsize:(g + 1) * gsize])

    groups = [fresh(g) for g in range(conc // gsize)]
    events: list = []
    witness: list = []
    append = events.append
    # thread -> [f, key, value, outcome, result, inv pos]; outcome None
    # until the op's instant, then "ok", "fail" or "info"
    open_: dict = {}
    budget = n = p["n_ops"]
    while budget > 0 or open_:
        r = rng.random()
        if budget > 0 and len(open_) < conc and (r < 0.45 or not open_):
            t = rng.choice([q for q in range(conc) if q not in open_])
            g = t // gsize
            gr = groups[g]
            procs = gr.procs.union(proc[g * gsize:(g + 1) * gsize])
            if gr.emitted >= gr.limit or len(procs) > plim:
                gr = groups[g] = fresh(g)
                procs = gr.procs
            gr.procs = procs
            gr.emitted += 1
            if t % gsize < readers:
                f, v = "read", None
            elif rng.random() < 1 / 3:
                f, v = "write", rng.randrange(nval)
            else:
                f, v = "cas", [rng.randrange(nval), rng.randrange(nval)]
            open_[t] = [f, gr.key, v, None, None, len(events)]
            append(("invoke", proc[t], f, (gr.key, v)))
            budget -= 1
            continue
        unapplied = [q for q, o in open_.items() if o[3] is None]
        if unapplied and (r < 0.75 or len(unapplied) == len(open_)):
            o = open_[rng.choice(unapplied)]
            f, k, v = o[0], o[1], o[2]
            partitioned = ((n - budget) // span) % 2
            if rng.random() < info_p[partitioned]:
                o[3] = "info"
                if rng.random() >= effect_p:
                    continue  # indeterminate, and never took effect
            cur = values.get(k)
            if f == "read":
                o[4] = cur
            elif f == "write":
                values[k] = v
            elif v[0] == cur:
                values[k] = v[1]
            else:
                o[3] = o[3] or "fail"
                continue
            o[3] = o[3] or "ok"
            witness.append(o[5])
            continue
        applied = [q for q, o in open_.items() if o[3] is not None]
        if not applied:
            continue
        t = rng.choice(applied)
        f, k, v, outcome, result, _pos = open_.pop(t)
        if outcome == "ok":
            append(("ok", proc[t], f, (k, result if f == "read" else v)))
        else:
            append((outcome, proc[t], f, (k, v)))
            if outcome == "info":
                proc[t] += conc
    return events, witness


def key_stats(events: list) -> dict:
    """Per key: (ops, crashed ops, distinct processes)."""
    ops: dict = {}
    crashed: dict = {}
    procs: dict = {}
    for t, pr, _f, (k, _v) in events:
        if t == "invoke":
            ops[k] = ops.get(k, 0) + 1
            procs.setdefault(k, set()).add(pr)
        elif t == "info":
            crashed[k] = crashed.get(k, 0) + 1
    return {k: (ops[k], crashed.get(k, 0), len(procs[k])) for k in ops}


def variant(p: dict, events: list, seed: int, key) -> list:
    """The base history with its keys renamed by a bijection drawn from
    (seed, key), each key's values by a permutation of [0, n_values)
    drawn for the key and the process ids by another bijection. The
    variant keeps the event order exactly, so every key's history is
    the base's up to the renaming: the batch launches the same rows and
    shapes and leaves the same members undecided on every seed, while
    no answer keyed on the history can be replayed."""
    assert p["n_values"] == 5, "the value permutations are of range(5)"
    rng = random.Random(f"{seed}/variant/{key}")
    ka, kb, ma, mb, pa, pb = (rng.randrange(1, _M) for _ in range(6))
    keys: dict = {}  # key -> (its new name, its value permutation)

    def val(v, perm):
        if v is None:
            return None
        if isinstance(v, list):
            return [perm[v[0]], perm[v[1]]]
        return perm[v]

    out = []
    for t, pr, f, (k, v) in events:
        kv = keys.get(k)
        if kv is None:
            kv = keys[k] = ((ka * k + kb) % _M,
                            _PERMS[((ma * k + mb) % _M) % len(_PERMS)])
        out.append((t, (pa * pr + pb) % _M, f, (kv[0], val(v, kv[1]))))
    return out


def compare(cell, items) -> None:
    """items: (events, witness, program result) per verdict. The
    reference splits each history by key and decides every key by
    proof (the generator's order replayed; its own search where that
    does not replay), and replays each key's certificate. Records two
    exact checks on the cell, counted per key."""
    ref = cell.reference
    mismatch = cert_faults = keys = 0
    for i, (events, witness, res) in enumerate(items):
        split = ref.split(events)
        keys += len(split)
        want = ref.verdicts(split, witness)
        got = (res or {}).get("results") or {}
        for k in sorted(set(want) | set(got), key=str):
            r = got.get(k)
            v = None if not isinstance(r, dict) else r.get("valid?")
            if k not in want or v is not want[k]:
                mismatch += 1
                if mismatch <= 3:
                    cell.log(f"verdict {i} key {k!r}: program {v!r}, "
                             f"reference {want.get(k)!r}")
                continue
            why = ref.replay_key(split[k], k, len(events), r)
            if why is not None:
                cert_faults += 1
                if cert_faults <= 3:
                    cell.log(f"certificate {i} key {k!r}: {why}")
        if (res or {}).get("valid?") is not all(want.values()):
            mismatch += 1
            cell.log(f"verdict {i}: program {(res or {}).get('valid?')!r}"
                     f", reference {all(want.values())!r}")
    cell.log(f"compared {len(items)} verdicts, {keys} keys, with the "
             "reference")
    cell.check("verdict_mismatches", mismatch, 0)
    cell.check("certificate_faults", cert_faults, 0)
