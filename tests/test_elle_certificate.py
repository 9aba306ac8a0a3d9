"""The certificate of a valid list-append verdict from the device engine:
a serialization, ordered by the verdict's own dependency graph, that
replays under list-append semantics where completion order does not; the
C topological order against its Python reference; a forged order
rejected; invalid certificates as before."""

import copy
import random

import numpy as np
import pytest

from jepsen_tpu import native
from jepsen_tpu.history import History, op
from jepsen_tpu.tpu import certify, elle, elle_device, synth

DEVICE = {"certify": True, "engine": "device"}


def _hist(events):
    return History([op(index=i, time=i, type=t, process=p, f="txn",
                       value=v) for i, (t, p, v) in enumerate(events)])


def _early_commit_late_completion():
    """A reads x while it is empty and commits first, but completes
    after B, whose append to x it did not see: completion order puts A
    after B, and a replay of that order gives A's read [1], not []."""
    return _hist([
        ("invoke", 0, [["r", "x", None]]),
        ("invoke", 1, [["append", "x", 1]]),
        ("ok", 1, [["append", "x", 1]]),
        ("invoke", 2, [["r", "x", None]]),
        ("ok", 2, [["r", "x", [1]]]),
        ("ok", 0, [["r", "x", []]]),
    ])


def test_valid_order_replays_where_completion_order_does_not():
    h = _early_commit_late_completion()
    res = elle.check_list_append(h, DEVICE)
    assert res["valid?"] is True
    cert = res["certificate"]
    assert cert["serialization"] == [0, 1, 3]
    certify.validate(h, cert)
    by_completion = copy.deepcopy(cert)
    by_completion["serialization"] = [1, 3, 0]
    with pytest.raises(certify.CertificateError, match="does not replay"):
        certify.validate(h, by_completion)


def test_info_txn_that_took_effect_is_serialized():
    """An :info txn whose append a committed read saw is in the order,
    after every txn that completed before its invocation; one that no
    read saw is not."""
    h = _hist([
        ("invoke", 0, [["append", "x", 1]]),
        ("ok", 0, [["append", "x", 1]]),
        ("invoke", 1, [["append", "x", 2], ["append", "y", 1]]),
        ("info", 1, [["append", "x", 2], ["append", "y", 1]]),
        ("invoke", 2, [["append", "y", 2]]),
        ("info", 2, [["append", "y", 2]]),
        ("invoke", 3, [["r", "x", None]]),
        ("ok", 3, [["r", "x", [1, 2]]]),
    ])
    res = elle.check_list_append(h, DEVICE)
    assert res["valid?"] is True
    assert res["certificate"]["serialization"] == [0, 2, 6]
    certify.validate(h, res["certificate"])


def _random_graph(rng, n, m, acyclic):
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    if acyclic:
        src, dst = np.minimum(src, dst), np.maximum(src, dst)
    keep = src != dst
    return src[keep], dst[keep]


@pytest.mark.skipif(native.jlog() is None, reason="no C toolchain")
@pytest.mark.parametrize("seed,acyclic", [(0, True), (1, True), (2, False),
                                          (3, True)])
def test_c_topological_order_equals_python(seed, acyclic):
    rng = np.random.default_rng(seed)
    n = 500
    src, dst = _random_graph(rng, n, 3000, acyclic)
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    c = native.topo_order(n, src, dst)
    py = elle._topo_order_py(n, src, dst)
    assert c.tolist() == py.tolist()
    assert (len(c) == n) == acyclic
    pos = np.empty(n, dtype=np.int64)
    pos[c] = np.arange(len(c))
    if acyclic:
        assert (pos[src] < pos[dst]).all()


@pytest.mark.skipif(native.jlog() is None, reason="no C toolchain")
def test_c_topological_order_equals_python_on_a_verdict_graph():
    h = synth.list_append_history(2500, seed=3)
    _res, a = elle_device.analyze_list_append(h)
    assert (native.topo_order(a.n, a.edge_src, a.edge_dst).tolist()
            == elle._topo_order_py(a.n, a.edge_src, a.edge_dst).tolist())


def test_forged_order_swapping_conflicting_txns_rejected():
    h = synth.list_append_history(2500, seed=8)
    res = elle.check_list_append(h, {"certify": True})
    cert = res["certificate"]
    certify.validate(h, cert)
    order = cert["serialization"]
    txns = certify._collect_txns(h)

    def keys(i, kind):
        return {m[1] for m in txns[i]["mops"] if m[0] == kind}

    j = next(j for j in range(len(order) - 1)
             if txns[order[j + 1]]["type"] == "ok"
             and keys(order[j], "append") & keys(order[j + 1], "r"))
    forged = copy.deepcopy(cert)
    s = forged["serialization"]
    s[j], s[j + 1] = s[j + 1], s[j]
    with pytest.raises(certify.CertificateError):
        certify.validate(h, forged)
    dropped = copy.deepcopy(cert)
    dropped["serialization"].pop(j)
    with pytest.raises(certify.CertificateError):
        certify.validate(h, dropped)


def _cyclic(n_txns=2500, seed=4):
    """A valid history with a G1c planted at its end: two concurrent
    txns that each read the other's append."""
    rng = random.Random(seed)
    h = synth.list_append_history(n_txns, seed=seed)
    ops = list(h)
    n = len(ops)
    a, b = rng.randrange(10**9, 2 * 10**9), rng.randrange(10**9, 2 * 10**9)
    ext = [("invoke", 900, [["append", "x", a], ["r", "y", None]]),
           ("invoke", 901, [["append", "y", b], ["r", "x", None]]),
           ("ok", 900, [["append", "x", a], ["r", "y", [b]]]),
           ("ok", 901, [["append", "y", b], ["r", "x", [a]]])]
    ops += [op(index=n + i, time=n + i, type=t, process=p, f="txn",
               value=v) for i, (t, p, v) in enumerate(ext)]
    return History(ops)


def test_invalid_certificates_unchanged():
    h = _cyclic()
    res, a = elle_device.analyze_list_append(h)
    assert res["valid?"] is False
    with_graph = certify.elle_certificate(h, res, "list-append", graph=a)
    assert with_graph == certify.elle_certificate(h, res, "list-append")
    assert with_graph["cycle"] and "serialization" not in with_graph
    certify.validate(h, with_graph)


def test_host_engine_keeps_its_order_of_committed_txns():
    h = synth.list_append_history(300, seed=4)
    res = elle.check_list_append(h, {"certify": True, "engine": "host"})
    assert res["valid?"] is True
    assert "topo-order" in res["certificate"]
    assert "serialization" not in res["certificate"]
    certify.validate(h, res["certificate"])


def test_schema_accepts_a_serialization():
    h = _early_commit_late_completion()
    cert = elle.check_list_append(h, DEVICE)["certificate"]
    certify.validate_schema(cert)
    bad = dict(cert)
    del bad["serialization"]
    with pytest.raises(certify.CertificateError, match="serialization"):
        certify.validate_schema(bad)
