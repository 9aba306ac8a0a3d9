"""The elle-append-500k cell at a small size on the CPU: sound runs read
`correct` true, traced and untraced; a control and planted faults read
it false; the cell's per-layer readers read None without their spans or
counters and a number with them; the SCC byte count is the hand count
of a small bucket."""

import contextlib
import os
import types

import pytest

import cells
from benchlib import appends, harness, scc_work

CELL = "elle-append-500k"
# 4,000 txns: 8,000 events and some 30k edges, so the device engine and
# the SCC kernel both run
SMALL = {"n_txns": 4000, "warmup_history_seed": 11,
         "history_seeds": [12, 13]}
METRICS = os.path.join(harness.BENCH, "metrics")


def run(trace=0, seed=2**31 + 7):
    return cells.run_small(CELL, seed=seed, trace=trace, overrides=SMALL)


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct(trace):
    res = run(trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    if trace:
        m = res["metrics"]
        for name in ("flatten_ms.elle", "edges_ms.elle",
                     "scc_sweeps.elle", "certify_ms.elle"):
            assert m[name]["value"] > 0, name
    else:
        assert set(res["metrics"]) == {"lin_events_per_s", "setup_s"}


def _scc_work(events, witness):
    """(scc.sweeps, scc.rounds, the dependency graph as (src, dst, type)
    triples) of one verdict of the history."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.tpu import elle_device

    telemetry.reset()
    res, a = elle_device.analyze_list_append(
        appends.program_input((events, witness)))
    assert res["valid?"] is True
    c = telemetry.get().counters()
    graph = frozenset(zip(a.edge_src.tolist(), a.edge_dst.tolist(),
                          a.edge_ty.tolist()))
    return c["scc.sweeps"], c["scc.rounds"], graph


def test_variants_ask_for_the_same_work():
    """Three variants of one base give the SCC kernel the base's own
    graph, node for node, and it runs the base's sweeps and rounds on
    each; each variant's history still differs from the base's and from
    the others'. A variant that reordered completions would renumber
    the graph's nodes and fail here."""
    p = dict(harness.load_json(os.path.join(
        harness.BENCH, "traffic", "append-500k.json")), **SMALL)
    events, witness = appends.base(p, 12)
    sweeps, rounds, graph = _scc_work(events, witness)
    assert sweeps >= 2 * rounds >= 2
    seen = {repr(events)}
    for seed in (2**33, 2**33 + 1, 5):
        ev = appends.variant(p, events, seed, 0)
        assert [e[0] for e in ev] == [e[0] for e in events]
        seen.add(repr(ev))
        assert _scc_work(ev, witness) == (sweeps, rounds, graph)
    assert len(seen) == 4


@contextlib.contextmanager
def _without_realtime():
    """The control: the program with the realtime order left out of its
    dependency graph, so its serialization need not keep it."""
    from jepsen_tpu.tpu import elle, elle_device

    real = elle_device.order_edges_from_arrays

    def session_only(*a):
        src, dst, ty = real(*a)
        keep = ty != elle.RT
        return src[keep], dst[keep], ty[keep]

    with cells.patched(elle_device, "order_edges_from_arrays", session_only):
        yield


def test_control_without_realtime_is_not_correct():
    with _without_realtime():
        res = run()
    assert not res["correct"]
    assert res["checks"]["certificate_faults"]["value"] >= 1


@contextlib.contextmanager
def _fault(kind):
    """'flip' (the verdict inverted) or 'stale' (the previous verdict's
    answer returned again: an answer taken from another base, since the
    window's verdicts alternate between two) where the program produces
    its answer."""
    from jepsen_tpu.checker import cycle

    real = cycle.append_checker
    last: dict = {}

    def checker(opts=None):
        inner = real(opts)

        def check(*a, **kw):
            res = inner.check(*a, **kw)
            if kind == "flip":
                return dict(res, **{"valid?": not res.get("valid?")})
            prev = last.get("r", res)
            last["r"] = res
            return prev

        return types.SimpleNamespace(check=check)

    with cells.patched(cycle, "append_checker", checker):
        yield


@pytest.mark.parametrize("kind", ["flip", "stale"])
def test_fault_is_not_correct(kind):
    with _fault(kind):
        res = run()
    assert not res["correct"], res["checks"]


def reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


def cell(counters=None, spans=(), trace=None, launches=None):
    c = harness.Cell.__new__(harness.Cell)
    c.records = [{"events": 1_000_000}, {"events": 1_000_000}]
    c.counters = dict(counters or {})
    c.spans = list(spans)
    c.trace_summary = dict(trace or {})
    c.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    if launches is not None:
        c.scc_launches = launches
    return c


MS = 1_000_000
TRACE = {"window_s": 4.0, "busy_s": {0: 1.0},
         "programs": {"jit_scc_peel": 0.05, "jit__kernel": 1.0},
         "idle_by_span": {"elle.edges": 2.5, "(no program span)": 0.5}}
LAUNCH = {"kernel": "scc", "bucket": repr(("scc", 524288, 3801088)),
          "sweeps": 4, "rounds": 2}


@pytest.mark.parametrize("name,with_it,want", [
    ("flatten_ms.elle", cell(spans=[("elle.flatten", 0, 300 * MS)]), 150.0),
    ("edges_ms.elle", cell(spans=[("elle.edges", 0, 500 * MS),
                                  ("elle.order-edges", 0, 100 * MS)]),
     300.0),
    ("certify_ms.elle", cell(spans=[("certify.attach", 0, 800 * MS)]),
     400.0),
    ("scc_sweeps.elle", cell({"scc.sweeps": 96}), 48.0),
    ("scc_device_ms.elle", cell(trace=TRACE), 25.0),
    ("device_idle_share.elle", cell(trace=TRACE), 75.0),
    ("unspanned_idle_share.elle", cell(trace=TRACE), 12.5),
    ("scc_hbm_share.elle", cell(trace=TRACE, launches=[LAUNCH]),
     100.0 * 4 * (9 * 3801088 + 8 * 524288) / (0.05 * 819e9)),
])
def test_reader_reads_a_number_with_its_source(name, with_it, want):
    assert reader(name).read(with_it) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "flatten_ms.elle", "edges_ms.elle", "certify_ms.elle",
    "scc_sweeps.elle", "scc_device_ms.elle", "device_idle_share.elle",
    "unspanned_idle_share.elle", "scc_hbm_share.elle"])
def test_reader_reads_none_without_its_source(name):
    # a run with no such span, counter, trace or sweep count: the parent
    # program's SCC launch records carry no sweeps and its jit name is
    # not the kernel's
    parent = dict(TRACE, programs={"jit_full": 0.05})
    assert reader(name).read(cell()) is None
    if name in ("scc_device_ms.elle", "scc_hbm_share.elle"):
        assert reader(name).read(cell(
            trace=parent, launches=[dict(LAUNCH, sweeps=None)])) is None


def test_scc_bytes_are_the_hand_count_of_a_small_bucket():
    # a sweep over 16 padded edges and 8 padded nodes streams src and
    # dst (4 bytes each) and the mask (1 byte) of every edge, and reads
    # and writes each node's colour (4 bytes each way): 208 bytes
    rec = {"bucket": repr(("scc", 8, 16)), "sweeps": 3}
    assert scc_work.sweep_bytes(8, 16) == 16 * (4 + 4 + 1) + 8 * (4 + 4)
    assert scc_work.launch_bytes([rec]) == 3 * 208
    sharded = {"bucket": repr(("scc-sharded", 4, 8, 16)), "sweeps": 3}
    assert scc_work.launch_bytes([sharded]) == 3 * (16 * 9 // 4 + 64)
    assert scc_work.launch_bytes([{"bucket": rec["bucket"]}]) is None


def test_scc_bytes_of_a_real_launch():
    """A launch record of the program's kernel carries its sweep count
    and its bucket; the count is the sweeps times the bucket's sweep
    bytes."""
    import numpy as np

    from jepsen_tpu.tpu import profiler, scc

    profiler.reset()
    rng = np.random.default_rng(0)
    n, e = 3000, 25_000
    scc.scc_device(n, rng.integers(0, n, e), rng.integers(0, n, e))
    rec = [r for r in profiler.get().records() if r["kernel"] == "scc"][-1]
    assert scc_work.bucket(rec) == (4096, 32768, 1)
    assert rec["sweeps"] >= 2 * rec["rounds"] >= 2
    assert scc_work.launch_bytes([rec]) == rec["sweeps"] * (
        9 * 32768 + 8 * 4096)
