"""The stage spans of a list-append verdict and the SCC kernel's own work
counts: the span tree of a valid and of an invalid verdict on the device
engine, scc.sweeps and scc.rounds from the kernel (absent on the host
path), the host engine counted, and the spans mirrored into a captured
JAX profiler trace."""

import collections
import glob
import os

import jax
import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.history import History, op
from jepsen_tpu.tpu import elle, profiler, scc, synth


@pytest.fixture
def fresh(monkeypatch):
    # the SCC takes the device kernel at this test's size
    monkeypatch.setattr(scc, "DEVICE_MIN_EDGES", 1)
    telemetry.reset()
    profiler.reset()
    yield telemetry.get()
    telemetry.reset()
    profiler.reset()


def _valid():
    return synth.list_append_history(2500, seed=6)


def _invalid():
    """The valid history with a G1c appended: two concurrent txns that
    each read the other's append."""
    ops = list(_valid())
    n = len(ops)
    ext = [("invoke", 900, [["append", "x", 1], ["r", "y", None]]),
           ("invoke", 901, [["append", "y", 1], ["r", "x", None]]),
           ("ok", 900, [["append", "x", 1], ["r", "y", [1]]]),
           ("ok", 901, [["append", "y", 1], ["r", "x", [1]]])]
    ops += [op(index=n + i, time=n + i, type=t, process=p, f="txn",
               value=v) for i, (t, p, v) in enumerate(ext)]
    return History(ops)


def _tree(events):
    by_id = {e["id"]: e for e in events}
    return [(e["name"], by_id.get(e["parent"], {}).get("name"))
            for e in sorted(events, key=lambda e: e["t0"])]


STAGES = ("elle.analysis", "elle.flatten", "elle.edges", "elle.order-edges",
          "elle.scc", "elle.cycles", "certify.attach")


def test_valid_verdict_span_tree(fresh):
    res = elle.check_list_append(_valid(), {"certify": True})
    assert res["valid?"] is True and "serialization" in res["certificate"]
    tree = [(n, p) for n, p in _tree(fresh.events()) if n in STAGES]
    # a valid verdict costs one SCC and no cycle search
    assert tree == [("elle.analysis", None),
                    ("elle.flatten", "elle.analysis"),
                    ("elle.edges", "elle.analysis"),
                    ("elle.order-edges", "elle.analysis"),
                    ("elle.scc", "elle.analysis"),
                    ("certify.attach", None)]
    names = {n for n, _p in _tree(fresh.events())}
    assert "elle:list-append" not in names
    # the kernel's launch record sits inside its elle.scc span
    ev = {e["name"]: e for e in fresh.events()}
    k, s = ev["kernel:scc"], ev["elle.scc"]
    assert s["t0"] <= k["t0"] and k["t1"] <= s["t1"]


def test_invalid_verdict_span_tree(fresh):
    res = elle.check_list_append(_invalid(), {"certify": True})
    assert res["valid?"] is False and res["certificate"]["cycle"]
    tree = [(n, p) for n, p in _tree(fresh.events()) if n in STAGES]
    assert tree[:5] == [("elle.analysis", None),
                        ("elle.flatten", "elle.analysis"),
                        ("elle.edges", "elle.analysis"),
                        ("elle.order-edges", "elle.analysis"),
                        ("elle.scc", "elle.analysis")]
    assert tree[5] == ("elle.cycles", "elle.analysis")
    # the graded subsets: one SCC each below the full graph's
    inner = tree[6:-1]
    assert inner and set(inner) == {("elle.scc", "elle.cycles")}
    assert tree[-1] == ("certify.attach", None)


def test_scc_counts_on_the_device_path(fresh):
    elle.check_list_append(_invalid())
    c = fresh.counters()
    launches = profiler.get().records()
    scc_recs = [r for r in launches if r["kernel"] == "scc"]
    assert c["profiler.scc.launches"] == len(scc_recs) >= 2
    assert c["scc.sweeps"] == sum(r["sweeps"] for r in scc_recs) > 0
    assert c["scc.rounds"] == sum(r["rounds"] for r in scc_recs) > 0
    # a fixpoint sweeps at least once forward and once backward a round
    assert all(r["sweeps"] >= 2 * r["rounds"] >= 2 for r in scc_recs)
    assert "elle.host-engine" not in c


@pytest.mark.parametrize("opts", [{"engine": "host"}, {}])
def test_host_path_has_no_scc_counts(fresh, opts):
    # engine host, or a history below the device engine's size
    hist = _valid() if opts else synth.list_append_history(200, seed=2)
    res = elle.check_list_append(hist, dict(opts, certify=True))
    assert res["valid?"] is True
    c = fresh.counters()
    assert "scc.sweeps" not in c and "scc.rounds" not in c
    # the host engine was asked for, or chosen by size: no fall counted
    assert "elle.host-engine" not in c
    names = {e["name"] for e in fresh.events()}
    assert {"elle.analysis", "certify.attach"} <= names
    assert not names & {"elle.flatten", "elle.edges", "elle.scc"}


def test_unvectorizable_history_counts_the_host_engine(fresh):
    ops = list(_valid())
    n = len(ops)
    mops = [["append", "x", "not-an-int"]]
    ops += [op(index=n, time=n, type="invoke", process=999, f="txn",
               value=mops),
            op(index=n + 1, time=n + 1, type="ok", process=999, f="txn",
               value=mops)]
    res = elle.check_list_append(History(ops))
    assert res["valid?"] is True
    assert fresh.counters()["elle.host-engine"] == 1


def _host_events(trace_dir) -> collections.Counter:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    names = collections.Counter()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    names[e.name] += 1
    return names


def test_spans_in_a_captured_trace(fresh, tmp_path):
    hist = _invalid()
    with jax.profiler.trace(str(tmp_path)):
        elle.check_list_append(hist, {"certify": True})
    held = collections.Counter(e["name"] for e in fresh.events()
                               if e["name"] in STAGES)
    assert set(held) == set(STAGES)
    host = _host_events(tmp_path)
    for name, n in held.items():
        assert host[name] == n, (name, host[name], n)


def _read_elems(hist) -> int:
    return sum(len(m[2]) for o in hist if o.type == "ok"
               for m in o.value if m[0] == "r" and m[2] is not None)


def _off_spine_read(hist):
    """The history with one more committed read, of the key with the
    longest read: that read less its last element, with its first two
    elements swapped. Returns it and the elements off the spine."""
    ops = list(hist)
    spine = max((m for o in ops if o.type == "ok" for m in o.value
                 if m[0] == "r" and m[2] is not None),
                key=lambda m: len(m[2]))
    read = list(spine[2][:-1])
    read[0], read[1] = read[1], read[0]
    off = sum(a != b for a, b in zip(read, spine[2]))
    n = len(ops)
    ops += [op(index=n, time=n, type="invoke", process=902, f="txn",
               value=[["r", spine[1], None]]),
            op(index=n + 1, time=n + 1, type="ok", process=902, f="txn",
               value=[["r", spine[1], read]])]
    return History(ops), off


def test_read_elements_resolve_by_spine_position(fresh):
    hist = _valid()
    res = elle.check_list_append(hist, {"certify": True})
    assert res["valid?"] is True
    c = fresh.counters()
    assert c["elle.read-elems"] == _read_elems(hist) > 0
    assert c["elle.read-elems.off-spine"] == 0
    telemetry.reset()
    hist, off = _off_spine_read(_valid())
    res = elle.check_list_append(hist)
    assert "incompatible-order" in res["anomaly-types"]
    c = telemetry.get().counters()
    assert c["elle.read-elems"] == _read_elems(hist)
    assert c["elle.read-elems.off-spine"] == off == 2
