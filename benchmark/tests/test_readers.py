"""The per-layer readers of the verdict's stage spans and host-search
rows, on hand-made cells: the value, 0.0 where the program recorded
none of that work, and None where the program does not record it."""

import os

import pytest

from benchlib import harness

METRICS = os.path.join(harness.BENCH, "metrics")


def reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


def cell(counters=None, spans=(), trace_summary=None, events=2_000_000):
    c = harness.Cell.__new__(harness.Cell)
    c.records = [{"events": events // 2}, {"events": events - events // 2}]
    c.counters = dict(counters or {})
    c.spans = list(spans)
    c.trace_summary = dict(trace_summary or {})
    return c


def summary(idle_by_span):
    return {"window_s": 20.0, "busy_s": {0: 14.0}, "programs": {},
            "idle_by_span": idle_by_span}


def test_unspanned_idle_share():
    read = reader("unspanned_idle_share.lin").read
    assert read(cell(trace_summary=summary(
        {"(no program span)": 1.0, "certify.attach": 5.0}))) == 5.0
    # a traced run whose every idle gap fell inside a span
    assert read(cell(trace_summary=summary(
        {"wgl.encode": 1.0, "certify.attach": 5.0}))) == 0.0
    # no trace, or a trace with no measured window
    assert read(cell()) is None
    assert read(cell(trace_summary={"window_s": 0.0})) is None


def test_host_reach_rows():
    read = reader("host_reach_rows.lin").read
    assert read(cell({"wgl.host-reach.rows": 30})) == 15.0
    assert read(cell({"wgl.host-reach.rows": 0})) == 0.0
    assert read(cell({"profiler.wgl-reach.launches": 8})) is None


def test_host_reach_ms():
    read = reader("host_reach_ms.lin").read
    spans = [("wgl.host-reach", 0, 3_000_000),
             ("wgl.host-reach", 5_000_000, 6_000_000),
             ("wgl.screen", 0, 9_000_000)]
    assert read(cell({"wgl.host-reach.rows": 2}, spans)) == 2.0
    assert read(cell({"wgl.host-reach.rows": 0})) == 0.0
    assert read(cell(spans=spans)) is None


def test_screen_ms():
    read = reader("screen_ms.lin").read
    spans = [("wgl.screen", 0, 40_000_000),
             ("wgl.screen", 50_000_000, 60_000_000),
             ("wgl.pack", 0, 1_000_000)]
    assert read(cell(spans=spans)) == pytest.approx(25.0)
    assert read(cell(spans=[("certify.attach", 0, 1)])) is None
    # no events decided: nothing to divide by
    assert read(cell(spans=spans, events=0)) is None
