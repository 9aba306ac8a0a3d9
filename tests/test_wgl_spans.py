"""The stage spans of a linearizability verdict: the span tree of a
segmented check, the rows counted for the exact host search, one encode
and pack span per chunk on the batch paths, and program spans mirrored
into a captured JAX profiler trace."""

import collections
import glob
import os

import jax
import numpy as np
import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.checker import models
from jepsen_tpu.tpu import profiler, synth, wgl

# a ~2,400-entry history cuts into 9 segments of 256 entries, so the
# segmented path runs in two early-exit waves ((0, 4) and (4, 9))
N_OPS = 3000
SEGMENTED_MIN_M = 1024


@pytest.fixture
def fresh():
    telemetry.reset()
    profiler.reset()
    yield telemetry.get()
    telemetry.reset()
    profiler.reset()


@pytest.fixture
def segmented(monkeypatch):
    """analysis() takes the segmented path at this test's size."""
    monkeypatch.setattr(wgl, "SEGMENT_MIN_M", SEGMENTED_MIN_M)
    return synth.register_history(N_OPS, n_procs=5, seed=42)


def _tree(events):
    by_id = {e["id"]: e for e in events}
    return by_id, [(e["name"], by_id.get(e["parent"], {}).get("name"))
                   for e in sorted(events, key=lambda e: e["t0"])]


def test_segmented_verdict_span_tree(fresh, segmented):
    out = wgl.analysis(models.cas_register(), segmented, certify=True)
    assert out["valid?"] is True and out["analyzer"] == "tpu-segmented"
    waves = wgl._wave_bounds(out["segments"], True)
    assert len(waves) == 2
    _by_id, tree = _tree(fresh.events())
    wgl_spans = [(n, p) for n, p in tree if n.startswith("wgl.")]
    names = collections.Counter(n for n, _p in wgl_spans)
    assert names["wgl.analysis"] == 1
    assert names["wgl.encode"] == 1
    assert names["wgl.segment"] == 1
    assert names["wgl.screen"] == len(waves)
    assert names["wgl.compose"] == len(waves)
    # a screen pack per wave plus the wave's main launch pack
    assert names["wgl.pack"] == 2 * len(waves)
    parents = {(n, p) for n, p in wgl_spans if n != "wgl.host-reach"}
    assert parents == {
        ("wgl.analysis", None),
        ("wgl.encode", "wgl.analysis"),
        ("wgl.segment", "wgl.analysis"),
        ("wgl.screen", "wgl.analysis"),
        ("wgl.pack", "wgl.screen"),
        ("wgl.pack", "wgl.analysis"),
        ("wgl.compose", "wgl.analysis"),
    }
    assert {p for n, p in wgl_spans if n == "wgl.host-reach"} <= {
        "wgl.screen", "wgl.compose"}
    # the stages run in order inside the verdict; certificates follow
    order = [n for n, _p in tree
             if n in ("wgl.encode", "wgl.segment", "wgl.screen",
                      "wgl.compose", "certify.attach")]
    assert order == (["wgl.encode", "wgl.segment"]
                     + ["wgl.screen", "wgl.compose"] * len(waves)
                     + ["certify.attach"])
    assert ("certify.attach", None) in tree
    assert (fresh.counters()["wgl.host-reach.rows"]
            == names["wgl.host-reach"])


def test_host_reach_rows_count_every_host_search(fresh, segmented,
                                                 monkeypatch):
    """Every row the device answers UNKNOWN falls to the exact host
    search: the counter and the spans equal the calls."""
    real_drain = wgl._drain

    def unknown_drain(out, reach):
        res = real_drain(out, reach)
        if reach:
            mask, unk = res
            return mask, np.ones_like(unk)
        return res

    calls = []
    real_search = wgl.search_host_reach

    def counted(enc):
        calls.append(enc.m)
        return real_search(enc)

    monkeypatch.setattr(wgl, "_drain", unknown_drain)
    monkeypatch.setattr(wgl, "search_host_reach", counted)
    out = wgl.analysis(models.cas_register(), segmented)
    assert out["valid?"] is True
    assert len(calls) > 0
    assert fresh.counters()["wgl.host-reach.rows"] == len(calls)
    spans = [e for e in fresh.events() if e["name"] == "wgl.host-reach"]
    assert len(spans) == len(calls)
    by_id, _tree_ = _tree(fresh.events())
    assert {by_id[e["parent"]]["name"] for e in spans} == {
        "wgl.screen", "wgl.compose"}


def test_host_reach_rows_start_at_zero(fresh):
    """A segmented check that sends no row to the host still reports
    the counter, at 0: absence means the counter was renamed."""
    from jepsen_tpu.tpu.encode import encode

    enc = encode(models.cas_register(),
                 synth.register_history(400, n_procs=3, seed=5))
    wgl.check_extend(enc, stride=128)
    assert "wgl.host-reach.rows" in fresh.counters()
    telemetry.reset()
    wgl.check_segmented(enc, target_len=128)
    c = fresh.counters()
    assert c["wgl.host-reach.rows"] == sum(
        1 for e in fresh.events() if e["name"] == "wgl.host-reach")


def test_batch_path_spans_once_per_chunk(fresh):
    hists = [synth.register_history(60, n_procs=3, seed=70 + i)
             for i in range(6)]
    out = wgl.analysis_batch_streamed(models.cas_register(), hists,
                                      chunk=3)
    assert [r["valid?"] for r in out] == [True] * 6
    names = collections.Counter(e["name"] for e in fresh.events())
    assert names["wgl.encode"] == 2
    assert names["wgl.pack"] == 2
    assert profiler.get().records()  # the chunks did launch


def test_ensemble_encodes_in_one_span(fresh):
    from jepsen_tpu.tpu import ensemble

    hists = [synth.register_history(40, n_procs=3, seed=90 + i)
             for i in range(5)]
    out = ensemble.analysis_batch_sharded(models.cas_register(), hists)
    assert all(r["valid?"] for r in out)
    names = collections.Counter(e["name"] for e in fresh.events())
    assert names["wgl.encode"] == 1
    assert names["wgl.pack"] == 1


class _SpyAnnotation(jax.profiler.TraceAnnotation):
    entered: list = []

    def __enter__(self):
        _SpyAnnotation.entered.append(self)
        return super().__enter__()


def test_no_trace_enters_no_annotation(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _SpyAnnotation)
    monkeypatch.setattr(_SpyAnnotation, "entered", [])
    t = telemetry.Telemetry()
    with t.span("untraced"):
        pass
    assert _SpyAnnotation.entered == []
    assert [e["name"] for e in t.events()] == ["untraced"]


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


def test_spans_mirrored_into_profiler_trace(fresh, segmented, tmp_path,
                                            monkeypatch):
    """While a profiler trace is capturing, every span is a host-plane
    event of the same name: a plain span, and every wgl.* stage span
    of a segmented verdict, as many times as telemetry recorded it."""
    monkeypatch.setattr(_SpyAnnotation, "entered", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _SpyAnnotation)
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("x-mirrored"):
            pass
        out = wgl.analysis(models.cas_register(), segmented)
    assert out["valid?"] is True
    host = _host_events(tmp_path)
    assert host["x-mirrored"] == 1
    spans = collections.Counter(e["name"] for e in fresh.events()
                                if e["name"].startswith("wgl."))
    assert spans["wgl.analysis"] == 1 and spans["wgl.screen"] >= 1
    for name, n in spans.items():
        assert host[name] == n, (name, n, host[name])
    # profiler records (kernel:*) are not mirrored: the device plane
    # has those launches
    assert len(_SpyAnnotation.entered) == sum(spans.values()) + 1
    assert not any(n.startswith("kernel:") for n in host)
