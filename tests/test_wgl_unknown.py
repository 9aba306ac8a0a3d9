"""Batch members the kernel leaves UNKNOWN (wgl.decide_unknown).

The order search of the certificate decides a valid member, and the
order it finds is the member's certificate order; an invalid member, or
one whose search runs out of budget, falls through to extract_witness
with today's result. The kernel is replaced by a launch that returns
one fixed answer for every row, so every member takes the host path.
"""

import numpy as np
import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.checker import models
from jepsen_tpu.tpu import certify, synth, wgl
from jepsen_tpu.tpu.encode import encode

MODEL = models.cas_register()
COUNTERS = ("wgl.batch.unknown", "wgl.batch.order", "wgl.batch.order-miss")


def key_history(seed: int, valid: bool):
    """One key's history: 5 processes, 40 ops, a fifth of them
    crashed; an invalid one reads a value no op wrote."""
    hist = synth.register_history(40, n_procs=5, seed=seed, crash_p=0.2)
    assert any(o.type == "info" for o in hist), seed
    if valid:
        return hist
    return synth.corrupt_register_history(hist, at_frac=0.5)[0]


def forced(monkeypatch, verdict: int) -> None:
    """Every launch answers `verdict` for each of its rows."""
    monkeypatch.setattr(wgl, "_launch",
                        lambda pb, rows, *a, **kw: len(rows))
    monkeypatch.setattr(wgl, "_drain",
                        lambda n, reach: np.full(n, verdict, np.int32))


def today(hist, verdict: int) -> dict:
    """The result the batch path gave before the order search: the
    witness search of extract_witness, under the row's analyzer."""
    out = wgl.extract_witness(encode(MODEL, hist))
    out["analyzer"] = ("tpu" if verdict == wgl.INVALID
                       else "tpu+host-fallback")
    return wgl._search_stats(out)


def counters() -> dict:
    c = telemetry.get().counters()
    return {name: c.get(name, 0) for name in COUNTERS}


CASES = ([("valid", s) for s in (1, 2, 3, 4)]
         + [("invalid", s) for s in (1, 2, 3)]
         + [("budget", s) for s in (1, 2)]
         + [("kernel-invalid", s) for s in (1, 2)])


@pytest.mark.parametrize("kind,seed", CASES)
def test_unknown_member(monkeypatch, kind, seed):
    valid = kind in ("valid", "budget")
    hist = key_history(seed, valid)
    enc = encode(MODEL, hist)
    assert wgl.search_host(enc)["valid?"] is valid
    verdict = wgl.INVALID if kind == "kernel-invalid" else wgl.UNKNOWN
    forced(monkeypatch, verdict)
    if kind == "budget":
        monkeypatch.setattr(certify, "SEARCH_BUDGET", 3)
    telemetry.reset()
    res = wgl.analysis_batch_streamed(MODEL, [hist], certify=True)[0]
    c = counters()
    cert = res.pop("certificate")
    assert wgl.ORDER_KEY not in res
    assert res["valid?"] is valid
    if kind == "valid":
        assert res == {"valid?": True, "witness-extraction": "order",
                       "analyzer": "tpu+host-fallback"}
        assert c == {"wgl.batch.unknown": 1, "wgl.batch.order": 1,
                     "wgl.batch.order-miss": 0}
        # the carried order is the certificate the search would give
        plain = certify.attach_wgl(MODEL, hist, enc,
                                   {"valid?": True})["certificate"]
        assert cert == plain and "absent" not in cert
        certify.validate(hist, cert)
        return
    assert res == today(hist, verdict)
    assert res["witness-extraction"] == "host"
    if kind == "kernel-invalid":
        # INVALID rows go straight to extract_witness
        assert c == {"wgl.batch.unknown": 0, "wgl.batch.order": 0,
                     "wgl.batch.order-miss": 0}
    else:
        assert c == {"wgl.batch.unknown": 1, "wgl.batch.order": 0,
                     "wgl.batch.order-miss": 1}
    if kind == "budget":
        assert cert == certify.absent("search-budget-exceeded")
    else:
        assert res["op-indices"] and res["configs"]
        certify.validate(hist, cert)


@pytest.mark.parametrize("certified", [True, False])
def test_unknown_batch_counts_sum(monkeypatch, certified):
    """A mixed batch: every UNKNOWN member is counted once, by the
    order search or by its miss, and no carried order is left on a
    result, certified or not."""
    hists = [key_history(s, s % 3 != 0) for s in range(1, 10)]
    forced(monkeypatch, wgl.UNKNOWN)
    telemetry.reset()
    res = wgl.analysis_batch_streamed(MODEL, hists, chunk=4,
                                      certify=certified)
    c = counters()
    assert c["wgl.batch.unknown"] == len(hists)
    assert c["wgl.batch.order"] + c["wgl.batch.order-miss"] == len(hists)
    assert c["wgl.batch.order"] == sum(s % 3 != 0 for s in range(1, 10))
    for hist, r in zip(hists, res):
        assert wgl.ORDER_KEY not in r
        assert r["valid?"] is wgl.search_host(encode(MODEL, hist))[
            "valid?"]
        assert ("certificate" in r) is certified
