"""Certificate prefetch: a segmented verdict's certificate work done on
the host while the device runs a wave's main launch. The certificate
must be byte-identical with and without it, on valid histories, on
histories whose wave-boundary reach sets are not singletons, on
invalid histories that exit early, and under an exhausted search
budget; the counters say how often it engaged."""

import json

import numpy as np
import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.checker import models
from jepsen_tpu.tpu import certify, profiler, synth, wgl
from jepsen_tpu.tpu.encode import encode

MODEL = models.cas_register()
# ~2,000 entries cut into 15 segments of ~128: three early-exit waves,
# (0, 4), (4, 12) and (12, 15)
N_OPS = 2500
TARGET_LEN = 128


class Recording(certify.Prefetch):
    """A Prefetch that keeps the reach sets it was offered."""

    def __init__(self, hist, enc):
        super().__init__(hist)
        self.enc = enc
        self.offered: list[list[int]] = []

    def offer(self, cuts, resolved, reaches):
        self.offered.append(list(reaches))
        super().offer(cuts, resolved, reaches)


class Polled:
    """A launch output whose readiness flips after `polls` polls."""

    def __init__(self, value, polls: int):
        self.value = np.asarray(value)
        self.polls = polls

    def is_ready(self) -> bool:
        self.polls -= 1
        return self.polls < 0

    def __array__(self, dtype=None, copy=None):
        return self.value if dtype is None else self.value.astype(dtype)


@pytest.fixture
def launches(monkeypatch):
    """launches(polls): every launch reports ready only after `polls`
    polls of its first output (0: ready at once)."""
    real = wgl._launch

    def install(polls: int):
        def launch(*a, **kw):
            out = real(*a, **kw)
            rec = profiler.get().take(out)
            slow = (Polled(out[0], polls),) + tuple(out[1:])
            return profiler.get().attach(slow, rec)

        monkeypatch.setattr(wgl, "_launch", launch)

    return install


@pytest.fixture
def fresh():
    telemetry.reset()
    yield telemetry.get()
    telemetry.reset()


def _dumps(cert: dict) -> str:
    return json.dumps(cert, sort_keys=True)


def _history(seed: int = 42, read_p: float = 0.4):
    return synth.register_history(N_OPS, n_procs=5, seed=seed,
                                  read_p=read_p)


def _check(hist):
    enc = encode(MODEL, hist)
    p = Recording(hist, enc)
    res = wgl.check_segmented(enc, target_len=TARGET_LEN, witness=True,
                              prefetch=p)
    return enc, res, p


def _singleton(mask: int) -> bool:
    return mask & (mask - 1) == 0


def _same_certificate(hist, enc, res, p) -> dict:
    with_p = certify.wgl_certificate(MODEL, hist, enc, res, p)
    without = certify.wgl_certificate(MODEL, hist, enc, res)
    assert _dumps(with_p) == _dumps(without)
    return with_p


def test_valid_certificate_identical(launches):
    launches(10 ** 6)  # the device never looks done: every job runs
    hist = _history()
    enc, res, p = _check(hist)
    assert res["valid?"] is True and res["segments"] == 15
    assert len(p.offered) == 3
    cert = _same_certificate(hist, enc, res, p)
    assert "absent" not in cert
    assert p.digest == certify.history_digest(hist)
    assert 0 < p.used < res["segments"]
    certify.validate(hist, cert)


def test_waits_for_a_later_singleton(launches):
    launches(10 ** 6)
    # few reads: the register's value is often unknown at a cut
    hist = _history(seed=7, read_p=0.03)
    enc, res, p = _check(hist)
    assert res["valid?"] is True
    after_wave1, after_wave2, _ = p.offered
    # neither wave boundary fixes the chain ...
    assert not _singleton(after_wave1[4])
    assert not _singleton(after_wave2[12])
    assert not any(_singleton(r) for r in after_wave1[1:])
    # ... a cut inside wave 2 does, so wave 3's launch hides its orders
    assert any(_singleton(r) for r in after_wave2[5:12])
    cert = _same_certificate(hist, enc, res, p)
    assert "absent" not in cert
    assert p.used > 0
    certify.validate(hist, cert)


def test_invalid_early_exit_identical(launches):
    launches(10 ** 6)
    hist, _bad = synth.corrupt_register_history(_history(), at_frac=0.45)
    enc, res, p = _check(hist)
    assert res["valid?"] is False
    # the witness is in wave 2: wave 3 never launched
    assert 4 <= res["failed-segment"] < 12 and len(p.offered) == 2
    cert = _same_certificate(hist, enc, res, p)
    assert cert["verdict"] == "invalid" and cert["segments"]
    assert p.used > 0  # pre-witness orders came from the prefetch
    certify.validate(hist, cert)


def test_search_budget_absent_identical(launches, monkeypatch):
    launches(10 ** 6)
    monkeypatch.setattr(certify, "SEARCH_BUDGET", 5)
    hist = _history()
    enc, res, p = _check(hist)
    assert res["valid?"] is True and len(p._queued) > 0
    assert not p.orders  # every order job ran out of budget, dropped
    cert = _same_certificate(hist, enc, res, p)
    assert cert == certify.absent("search-budget-exceeded")


@pytest.mark.parametrize("polls", [2, 0])
def test_engagement_counters(launches, fresh, polls):
    launches(polls)
    hist = _history()
    enc, res, p = _check(hist)
    out = certify.attach_wgl(MODEL, hist, enc, dict(res), p)
    counters = fresh.counters()
    segments = counters["certify.segments"]
    prefetched = counters["certify.segments.prefetched"]
    assert segments == res["segments"]
    if polls:
        # one job before each main launch looks done: the digest in
        # wave 1, then one segment order in each later wave
        assert 0 < prefetched <= segments
        assert any(e["name"] == "certify.prefetch"
                   for e in fresh.events())
    else:
        assert prefetched == 0 and p.digest is None
        assert not any(e["name"] == "certify.prefetch"
                       for e in fresh.events())
    assert _dumps(out["certificate"]) == _dumps(
        certify.wgl_certificate(MODEL, hist, enc, res))


def test_no_prefetch_no_certify_counters(fresh):
    hist = _history()
    res = wgl.check_segmented(encode(MODEL, hist), target_len=TARGET_LEN)
    assert res["valid?"] is True
    assert not [n for n in fresh.counters() if n.startswith("certify.")]
    assert not [e for e in fresh.events()
                if e["name"].startswith("certify.")]


def test_analysis_attaches_the_same_certificate(monkeypatch, launches,
                                                fresh):
    """Through the checker's entry point: wgl.analysis makes the
    prefetch, and its certificate is the one made without it."""
    launches(10 ** 6)
    monkeypatch.setattr(wgl, "SEGMENT_MIN_M", 1024)
    hist = synth.register_history(3000, n_procs=5, seed=42)
    out = wgl.analysis(MODEL, hist, certify=True)
    assert out["analyzer"] == "tpu-segmented"
    assert len(wgl._wave_bounds(out["segments"], True)) == 2
    cert = out.pop("certificate")
    again = certify.wgl_certificate(MODEL, hist, encode(MODEL, hist), out)
    assert _dumps(cert) == _dumps(again)
    counters = fresh.counters()
    assert counters["certify.segments"] == out["segments"]
    assert counters["certify.segments.prefetched"] > 0
