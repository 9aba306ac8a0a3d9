"""The list-append checker against the benchmark's plain Elle reference
(benchmark/reference/elle_list_append.py, loaded by path): seeded valid
histories of the simulated strict-serializable store, and the same
histories with one anomaly planted at their end, on keys still live and
by processes of the configured concurrency. Both sides must agree on the
verdict, the program's certificate must replay under the reference, and
each cycle the program reports must be a cycle of the reference's graph
that the reference names the same way."""

import importlib.util
import os
import sys

import pytest

from jepsen_tpu.tpu import certify, elle

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import appends  # noqa: E402


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "test_ref_" + name, os.path.join(BENCH, "reference", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("elle_list_append")

# the cell's mix at 2,500 txns: 5,000 events, so engine "auto" takes the
# device engine
P = {"n_txns": 2500, "concurrency": 10, "key_count": 3,
     "min_txn_length": 1, "max_txn_length": 4, "max_writes_per_key": 32,
     "info_p": 0.02, "fail_p": 0.02}


def _check(events):
    hist = appends.program_input((events, None))
    return hist, elle.check_list_append(hist, {"certify": True})


@pytest.mark.parametrize("seed", [5, 2**31 + 11, 77])
def test_valid_histories_agree_and_the_certificate_replays(seed):
    events, witness = appends.base(P, seed)
    events = appends.variant(P, events, seed, "t")
    txns = ref.transactions(events)
    assert ref.replay_order(txns, witness) is None
    assert ref.verdict(events, witness, txns=txns) is True
    s = ref.search(txns)
    assert not s["anomalies"] and not s["sccs"]
    hist, res = _check(events)
    assert res["valid?"] is True
    assert "serialization" in res["certificate"]
    assert ref.replay(events, res, txns=txns) is None
    certify.validate(hist, res["certificate"])


def _live(events, witness, conc):
    """(final list of each key, two live keys with their next values,
    the current process of three threads) at the history's end."""
    txns = ref.transactions(events)
    lists: dict = {}
    for i in witness:
        for f, k, v in txns[i].mops:
            if f == "append":
                lists.setdefault(k, []).append(v)
    top: dict = {}
    recent: list = []
    for typ, _p, _f, mops in events:
        for f, k, v in mops:
            if f == "append":
                top[k] = max(top.get(k, 0), v)
            if typ == "invoke":
                recent.append(k)
    live = list(dict.fromkeys(reversed(recent[-40:])))
    k1, k2 = sorted(live[:3], key=lambda k: top.get(k, 0))[:2]
    last: dict = {}
    for typ, p, _f, _v in events:
        last[p % conc] = p + conc if typ == "info" else p
    procs = [last[t] for t in range(3)]
    return lists, (k1, top.get(k1, 0) + 1), (k2, top.get(k2, 0) + 1), procs


def plant(kind, events, witness, conc=P["concurrency"]):
    """The history with one anomaly appended at its end."""
    lists, (k1, a), (k2, b), (pa, pb, pc) = _live(events, witness, conc)
    l1, l2 = list(lists.get(k1, [])), list(lists.get(k2, []))
    out = list(events)

    def both(ta, tb, done_a, done_b):
        out.extend([("invoke", pa, "txn", ta), ("invoke", pb, "txn", tb),
                    ("ok", pa, "txn", done_a), ("ok", pb, "txn", done_b)])

    def then(p, done):
        out.extend([("invoke", p, "txn", [[f, k, None if f == "r" else v]
                                          for f, k, v in done]),
                    ("ok", p, "txn", done)])

    if kind == "G1c":
        # each of two concurrent txns reads the other's append
        both([["append", k1, a], ["r", k2, None]],
             [["append", k2, b], ["r", k1, None]],
             [["append", k1, a], ["r", k2, l2 + [b]]],
             [["append", k2, b], ["r", k1, l1 + [a]]])
    elif kind == "G-single":
        # a stale read: B sees A's append to k2 but not to k1
        both([["append", k1, a], ["append", k2, b]],
             [["r", k1, None], ["r", k2, None]],
             [["append", k1, a], ["append", k2, b]],
             [["r", k1, l1], ["r", k2, l2 + [b]]])
        then(pc, [["r", k1, l1 + [a]]])
    elif kind == "G2-item":
        # write skew: each reads the key the other appends to, unchanged
        both([["r", k1, None], ["append", k2, b]],
             [["r", k2, None], ["append", k1, a]],
             [["r", k1, l1], ["append", k2, b]],
             [["r", k2, l2], ["append", k1, a]])
        then(pc, [["r", k1, l1 + [a]], ["r", k2, l2 + [b]]])
    elif kind == "incompatible-order":
        # two reads that order two appends to one key both ways round
        both([["append", k1, a]], [["append", k1, a + 1]],
             [["append", k1, a]], [["append", k1, a + 1]])
        then(pa, [["r", k1, l1 + [a]]])
        then(pb, [["r", k1, l1 + [a + 1]]])
    return out


EXPECTED = {"G1c": {"G1c"}, "G-single": {"G-single"},
            "G2-item": {"G2-item"},
            "incompatible-order": {"incompatible-order"}}


@pytest.mark.parametrize("kind", sorted(EXPECTED))
@pytest.mark.parametrize("seed", [3, 2**31 + 19])
def test_planted_anomaly_agrees(kind, seed):
    events, witness = appends.base(P, seed)
    events = plant(kind, events, witness)
    txns = ref.transactions(events)
    assert ref.verdict(events, witness, txns=txns) is False
    s = ref.search(txns)
    hist, res = _check(events)
    assert res["valid?"] is False
    assert set(res["anomaly-types"]) == EXPECTED[kind]
    # the non-cycle anomalies the two sides find are the same
    cyc_names = {n for n, recs in res["anomalies"].items()
                 if any(r.get("steps") for r in recs)}
    assert set(s["anomalies"]) == set(res["anomaly-types"]) - cyc_names
    assert bool(s["sccs"]) == bool(cyc_names)
    # each reported cycle is a cycle of the reference's graph, named as
    # the reference names it
    for name in cyc_names:
        for rec in res["anomalies"][name]:
            inv = [hist.invocation(o).index for o in rec["cycle"]]
            cycle = [(inv[j], inv[(j + 1) % len(inv)], st["type"])
                     for j, st in enumerate(rec["steps"])]
            assert ref.cycle_fault(txns, cycle, s["edges"]) is None
            assert ref.classify(cycle) == name
    # the certificate's cycle is justified by the reference's own edges
    assert ref.replay(events, res, txns=txns) is None
    if cyc_names:
        assert res["certificate"].get("cycle")
        certify.validate(hist, res["certificate"])


@pytest.mark.parametrize("cycle,name", [
    ([(1, 2, "ww"), (2, 1, "ww")], "G0"),
    ([(1, 2, "wr"), (2, 1, "ww")], "G1c"),
    ([(1, 2, "rw"), (2, 1, "wr")], "G-single"),
    ([(1, 2, "rw"), (2, 1, "rw")], "G2-item"),
    ([(1, 2, "rw"), (2, 1, "realtime")], "G-single-realtime"),
    ([(1, 2, "wr"), (2, 3, "process"), (3, 1, "ww")], "G1c-process"),
    ([(1, 2, "ww"), (2, 1, "process")], "G0-process"),
])
def test_classify_by_adya_and_elle(cycle, name):
    assert ref.classify(cycle) == name
