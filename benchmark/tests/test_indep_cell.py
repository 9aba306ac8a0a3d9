"""The indep-register-410k-partition and lin-register-1m-anomaly85 cells
at small sizes on the CPU: sound runs read `correct` true, traced and
untraced; a planted fault reads it false; variants of one base ask the
batch for the same work; the generator keeps Jepsen's process limit,
reserved readers and write:cas mix; the anomaly's plant is stale in
every variant; the cell's per-layer readers read None without their
spans or counters and a number with them."""

import os
import sys

import pytest

import cells
from benchlib import harness, indep, registers, stale

if harness.ROOT not in sys.path:  # the program, as a run imports it
    sys.path.insert(0, harness.ROOT)

CELL = "indep-register-410k-partition"
ANOMALY = "lin-register-1m-anomaly85"
# 3,000 ops: some 40 keys of at most 100 ops
SMALL = {"n_ops": 3000, "per_key_limit": 100, "nemesis_ops": 300,
         "warmup_history_seed": 7, "history_seeds": [8, 9]}
METRICS = os.path.join(harness.BENCH, "metrics")
DRIVER = harness.load_module(os.path.join(
    harness.BENCH, "drivers", "indep_stream.py"), "test_driver_indep")
INDEP = ["split_ms.indep", "encode_ms.indep", "wgl_device_ms.indep",
         "unknown_share.indep", "witness_ms.indep", "certify_ms.indep",
         "device_idle_share.indep", "unspanned_idle_share.indep"]


def params(traffic, **over):
    return dict(harness.load_json(os.path.join(
        harness.BENCH, "traffic", traffic + ".json")), **over)


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct(trace):
    res = cells.run_small(CELL, trace=trace, overrides=SMALL)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["batch_fallbacks"]["value"] == 0
    if trace:
        m = res["metrics"]
        for name in ("split_ms.indep", "encode_ms.indep",
                     "unknown_share.indep", "witness_ms.indep",
                     "certify_ms.indep"):
            assert m[name]["value"] >= 0, name
    else:
        assert set(res["metrics"]) == {"lin_events_per_s", "setup_s"}


def test_a_flipped_key_is_not_correct():
    """One key's verdict inverted where the program produces it."""
    from jepsen_tpu import independent

    real = independent.IndependentChecker.check

    def check(self, *a, **kw):
        res = real(self, *a, **kw)
        r = next(iter(res["results"].values()))
        r["valid?"] = not r["valid?"]
        return res

    with cells.patched(independent.IndependentChecker, "check", check):
        res = cells.run_small(CELL, overrides=SMALL)
    assert not res["correct"]
    assert res["checks"]["verdict_mismatches"]["value"] >= 1


def test_a_program_without_the_batch_counters_fails_after_warm_up():
    """The parent program keeps no wgl.batch.rows counter: the run stops
    after its warm-up verdict instead of reading a fallback it cannot
    see."""
    from jepsen_tpu import telemetry

    real = telemetry.count

    def count(name, n=1):
        if name != "wgl.batch.rows":
            real(name, n)

    telemetry.reset()  # what earlier runs of this process counted
    with cells.patched(telemetry, "count", count), \
            pytest.raises(RuntimeError, match="wgl.batch.rows"):
        cells.run_small(CELL, overrides=SMALL)


def _batch_work(events):
    """(per-key (ops, crashed, processes) in first-appearance order, the
    rows launched, the launch buckets, the members left UNKNOWN by
    first-appearance index) of one check through the checker."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.tpu import profiler

    telemetry.reset()
    profiler.reset()
    order = list(dict.fromkeys(k for _t, _p, _f, (k, _v) in events))
    out = DRIVER.check_history(registers.program_input((events, None)))
    c = telemetry.get().counters()
    stats = indep.key_stats(events)
    unknown = sorted(order.index(k) for k, r in out["results"].items()
                     if r["analyzer"] == "tpu+host-fallback")
    buckets = [r["bucket"] for r in profiler.get().records()
               if r["kernel"] == "wgl"]
    return ([stats[k] for k in order], c["wgl.batch.rows"], buckets,
            unknown, c["wgl.batch.unknown"])


def test_variants_ask_for_the_same_work():
    """Three variants of one base give the base's per-key entry and
    crashed counts, launch the same rows in the same bucket and leave
    the same members to the host; each variant still differs from the
    base and from the others."""
    p = params("indep-410k-partition", **SMALL)
    events, _witness = indep.base(p, 8)
    work = _batch_work(events)
    assert work[1] == len(work[0]) and work[3], work
    seen = {repr(events)}
    for seed in (2**33, 2**33 + 1, 5):
        ev = indep.variant(p, events, seed, 0)
        assert [e[0] for e in ev] == [e[0] for e in events]
        seen.add(repr(ev))
        assert _batch_work(ev) == work
    assert len(seen) == 4


def test_the_generator_keeps_jepsens_limits():
    """Process limit 20 a key, the first 5 threads of each group of 10
    only read, the others write or cas at 1:2, and a crash retires its
    process."""
    p = params("indep-410k-partition", **SMALL)
    events, _witness = indep.base(p, 8)
    assert max(n for _o, _c, n in indep.key_stats(events).values()) <= 20
    conc, gsize = p["concurrency"], p["group_size"]
    fs: dict = {"read": 0, "write": 0, "cas": 0}
    retired = set()
    for t, pr, f, _kv in events:
        assert pr not in retired
        if t == "invoke":
            assert (f == "read") == ((pr % conc) % gsize < p["readers"])
            fs[f] += 1
        elif t == "info":
            retired.add(pr)
    assert retired
    assert 1.6 < fs["cas"] / fs["write"] < 2.5


def test_the_anomaly_is_stale_in_every_variant():
    ref = harness.load_module(os.path.join(
        harness.BENCH, "reference", "cas_register.py"), "test_ref_reg")
    p = params("register-1m-anomaly85", n_ops=1500)
    events, witness = registers.base(p, p["warmup_history_seed"])
    assert ref.verdict(events, witness) is True
    planted, pos = stale.plant(events, witness, p["plant_at"])
    assert pos >= p["plant_at"] * len(events)
    assert planted[pos][3] != events[pos][3]
    for seed in (2**33, 2**33 + 1, 5):
        ev = registers.variant(p, planted, seed, 0)
        assert ref.decide(ev) is False
        assert ref.verdict(ev, witness) is False


def test_anomaly_run_is_correct():
    res = cells.run_small(ANOMALY, overrides={"n_ops": 3000,
                                              "kernels": ["wgl"]})
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


def cell(counters=None, spans=(), trace=None):
    c = harness.Cell.__new__(harness.Cell)
    c.records = [{"events": 1_000_000}, {"events": 1_000_000}]
    c.counters = dict(counters or {})
    c.spans = list(spans)
    c.trace_summary = dict(trace or {})
    return c


MS = 1_000_000
TRACE = {"window_s": 4.0, "busy_s": {0: 1.0},
         "programs": {"jit__kernel": 0.5},
         "idle_by_span": {"wgl.batch.witness": 2.5,
                          "(no program span)": 0.5}}
COUNTERS = {"wgl.batch.rows": 4000, "wgl.batch.unknown": 1000}


@pytest.mark.parametrize("name,with_it,want", [
    ("split_ms.indep", cell(spans=[("independent.split", 0, 300 * MS),
                                   ("independent.stamp", 0, 100 * MS)]),
     200.0),
    ("encode_ms.indep", cell(spans=[("wgl.encode", 0, 500 * MS),
                                    ("wgl.pack", 0, 100 * MS)]), 300.0),
    ("wgl_device_ms.indep", cell(trace=TRACE), 250.0),
    ("unknown_share.indep", cell(COUNTERS), 25.0),
    ("witness_ms.indep", cell(COUNTERS, spans=[
        ("wgl.batch.witness", 0, 800 * MS)]), 400.0),
    ("certify_ms.indep", cell(spans=[("certify.attach", 0, 600 * MS)]),
     300.0),
    ("device_idle_share.indep", cell(trace=TRACE), 75.0),
    ("unspanned_idle_share.indep", cell(trace=TRACE), 12.5),
])
def test_reader_reads_a_number_with_its_source(name, with_it, want):
    assert reader(name).read(with_it) == pytest.approx(want)


@pytest.mark.parametrize("name", INDEP)
def test_reader_reads_none_without_its_source(name):
    assert reader(name).read(cell()) is None
