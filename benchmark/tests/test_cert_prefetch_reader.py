"""The reader of cert_prefetch_share.lin on hand-made cells: a share in
[0, 100] where the program counts certificate segments, and None where
it keeps no such counter."""

import os

import pytest

from benchlib import harness

METRICS = os.path.join(harness.BENCH, "metrics")


def reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


def cell(counters=None):
    c = harness.Cell.__new__(harness.Cell)
    c.records = [{"events": 1_000_000}, {"events": 1_000_000}]
    c.counters = dict(counters or {})
    c.spans = []
    c.trace_summary = {}
    return c


@pytest.mark.parametrize("prefetched, share", [
    (28, 100.0 * 28 / 49), (0, 0.0), (49, 100.0)])
def test_cert_prefetch_share(prefetched, share):
    read = reader("cert_prefetch_share.lin").read
    assert read(cell({"certify.segments": 49,
                      "certify.segments.prefetched": prefetched})) == \
        pytest.approx(share)


@pytest.mark.parametrize("counters", [{"certify.extracted": 1}, {}])
def test_cert_prefetch_share_absent(counters):
    # a program with no such counter, as before the prefetch
    assert reader("cert_prefetch_share.lin").read(cell(counters)) is None
