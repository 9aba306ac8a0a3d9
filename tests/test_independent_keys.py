"""The independent-key register checker against the benchmark's plain
reference (benchmark/reference/independent_cas_register.py, loaded by
path): seeded histories of the benchmark's simulated key-value store
under a partition nemesis (10 threads a key, crashed ops, process limit
20), checked through `independent.checker(checker.linearizable(...))`.
Every key's verdict must be the reference's, every key's certificate
must pass `certify.validate` and the reference's replay, and the
check's spans and counters must be recorded. A stale read planted in
one key makes exactly that key invalid; a forced batch failure is
counted and still decides every key; a checker that ignores reads
fails the comparison."""

import importlib.util
import os
import sys
import types

import pytest

from jepsen_tpu import checker, independent, telemetry
from jepsen_tpu.checker import models
from jepsen_tpu.tpu import certify

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import indep, registers  # noqa: E402

spec = importlib.util.spec_from_file_location(
    "test_ref_independent_cas_register",
    os.path.join(BENCH, "reference", "independent_cas_register.py"))
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)

# the cell's mix cut to 3,000 ops: some 40 keys of at most 100 ops, the
# partitioned ones ended by the process limit
P = {"n_ops": 3000, "concurrency": 50, "group_size": 10, "readers": 5,
     "n_values": 5, "process_limit": 20, "per_key_limit": 100,
     "nemesis_ops": 300, "info_p_partitioned": 0.2,
     "info_p_healed": 0.01, "info_effect_p": 0.5}


def _check(events):
    hist = registers.program_input((events, None))
    res = independent.checker(checker.linearizable(
        {"model": models.cas_register()})).check({}, hist)
    return hist, res


def _certificates_replay(hist, events, split, res):
    digest = certify.history_digest(hist)
    for k, r in res["results"].items():
        certify.validate(hist, r["certificate"], digest=digest)
        assert ref.replay_key(split[k], k, len(events), r) is None, k


@pytest.fixture(scope="module")
def base():
    events, witness = indep.base(P, 7)
    return indep.variant(P, events, 2**31 + 5, "t"), witness


@pytest.mark.parametrize("seed", [7, 2**31 + 19])
def test_every_key_agrees_with_the_reference(seed):
    events, witness = indep.base(P, seed)
    stats = indep.key_stats(events)
    assert 32 <= len(stats) <= 64
    assert max(o for o, _c, _p in stats.values()) <= 100
    assert max(p for _o, _c, p in stats.values()) <= 20
    assert sum(c for _o, c, _p in stats.values()) > len(stats)
    events = indep.variant(P, events, seed, "t")
    split = ref.split(events)
    want = ref.verdicts(split, witness)
    assert all(want.values())
    hist, res = _check(events)
    assert res["valid?"] is True
    assert {k: r["valid?"] for k, r in res["results"].items()} == want
    _certificates_replay(hist, events, split, res)


def _plant_in_one_key(events, witness):
    """The history with one stale read planted in its largest key: the
    first ok read in the key's second half that some other value makes
    unlinearizable, as the reference's search decides; and the key."""
    split = ref.split(events)
    k, key = max(split.items(), key=lambda kv: len(kv[1].events))
    for i in range(len(key.events) // 2, len(key.events)):
        t, p, f, v = key.events[i]
        if t != "ok" or f != "read":
            continue
        for stale_v in range(P["n_values"]):
            planted = list(key.events)
            planted[i] = (t, p, f, stale_v)
            if stale_v != v and not ref.reg.decide(planted):
                out = list(events)
                out[key.positions[i]] = (t, p, f, (k, stale_v))
                return out, k
    raise AssertionError("no read of the key can be made stale")


def test_a_stale_read_is_invalid_at_its_key_alone(base):
    events, witness = base
    events, bad = _plant_in_one_key(events, witness)
    split = ref.split(events)
    want = ref.verdicts(split, witness)
    assert [k for k, v in want.items() if not v] == [bad]
    hist, res = _check(events)
    assert res["valid?"] is False
    assert res["failures"] == [bad]
    assert {k: r["valid?"] for k, r in res["results"].items()} == want
    _certificates_replay(hist, events, split, res)


def test_spans_and_counters_of_the_check(base):
    events, _witness = base
    telemetry.reset()
    _check(events)
    tel = telemetry.get()
    c = tel.counters()
    names = {e["name"] for e in tel.events()}
    keys = len(ref.split(events))
    assert c["independent.keys"] == keys
    assert c["wgl.batch.rows"] == keys
    assert 0 <= c["wgl.batch.unknown"] <= c["wgl.batch.host"] <= keys
    assert c.get("independent.batch-fallback", 0) == 0
    assert {"independent.check", "independent.split", "independent.stamp",
            "wgl.encode", "wgl.pack", "wgl.batch.drain",
            "certify.attach"} <= names
    witnessed = sum(1 for e in tel.events()
                    if e["name"] == "wgl.batch.witness")
    assert witnessed == c["wgl.batch.host"]


def test_a_failed_batch_is_counted_and_every_key_decided(monkeypatch):
    events, witness = indep.base(dict(P, n_ops=600), 11)
    split = ref.split(events)

    def boom(*_a, **_kw):
        raise RuntimeError("forced batch failure")

    monkeypatch.setattr(checker.Linearizable, "check_batch", boom)
    telemetry.reset()
    hist, res = _check(events)
    assert telemetry.get().counters()["independent.batch-fallback"] == 1
    assert {k: r["valid?"] for k, r in res["results"].items()} == \
        ref.verdicts(split, witness)
    assert all("independent-batch-fallback" in r["degradation"]
               for r in res["results"].values())
    _certificates_replay(hist, events, split, res)


def test_a_checker_that_ignores_reads_fails_the_comparison(base):
    """The control: the reference with reads not held to the register's
    value in the program's place, on a history with one stale read."""
    events, witness = base
    events, bad = _plant_in_one_key(events, witness)
    split = ref.split(events)
    got = ref.verdicts(split, witness, check_reads=False)
    assert got[bad] is True
    res = {"valid?": all(got.values()),
           "results": {k: {"valid?": v} for k, v in got.items()}}
    checks = []
    cell = types.SimpleNamespace(
        reference=ref, log=lambda _m: None,
        check=lambda n, v, lim: checks.append((n, v, lim)))
    indep.compare(cell, [(events, witness, res)])
    assert any(v > lim for _n, v, lim in checks)
    assert dict((n, v) for n, v, _l in checks)["verdict_mismatches"] >= 1
