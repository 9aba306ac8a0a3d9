"""Device-performance observability: the kernel profiler (per-launch
records, cache accounting, wall/device split), the profile CLI/table,
the Prometheus endpoint, and the metrics schema validator."""

from __future__ import annotations

import argparse
import json
import urllib.request

import numpy as np
import pytest

from jepsen_tpu import telemetry, util
from jepsen_tpu.checker import models
from jepsen_tpu.reports import profile as rprofile
from jepsen_tpu.reports import telemetry as rtel
from jepsen_tpu.tpu import profiler, scc, synth, wgl
from jepsen_tpu.tpu.encode import encode


@pytest.fixture
def fresh():
    """Fresh clocks + recorders; wgl's compiled-bucket set is cleared
    (and restored) so cache accounting is deterministic per test."""
    util.init_relative_time()
    telemetry.reset()
    profiler.reset()
    saved = set(wgl._compiled_buckets)
    wgl._compiled_buckets.clear()
    yield profiler.get()
    wgl._compiled_buckets.update(saved)


def _launch_small(seed=1):
    hist = synth.register_history(64, n_procs=3, seed=seed)
    enc = encode(models.cas_register(), hist)
    return wgl.check_batch([enc])


class TestLaunchRecords:
    def test_wgl_phase_fields_present_and_plausible(self, fresh):
        res = _launch_small()
        assert int(res[0]) == wgl.VALID
        recs = [r for r in fresh.records() if r["kernel"] == "wgl"]
        assert recs, "no wgl launch record"
        r = recs[0]
        # wall/device split: the pipeline phases all recorded, and sum
        # within the record's wall time (monotonic vs linear clocks
        # differ, so allow slack via presence + positivity only)
        for ph in ("h2d_ns", "dispatch_ns", "compute_ns", "d2h_ns"):
            assert r.get(ph, 0) > 0, ph
        assert r["t1"] > r["t0"]
        assert r["iterations"] > 0
        assert r["compile_ns"] > 0  # first launch of the bucket

    def test_encode_and_pack_accounted(self, fresh):
        _launch_small()
        c = telemetry.get().counters()
        assert c["profiler.encode.launches"] >= 1
        assert c["profiler.encode.wall_ns"] > 0
        assert c["profiler.encode.entries"] > 0
        assert c["profiler.pack.launches"] >= 1

    def test_cache_hit_miss_across_repeated_buckets(self, fresh):
        _launch_small(seed=1)
        assert fresh.cache_stats["wgl"] == {"hits": 0, "misses": 1}
        _launch_small(seed=2)  # same shape bucket -> hit
        assert fresh.cache_stats["wgl"] == {"hits": 1, "misses": 1}
        # a different shape bucket compiles anew
        hist = synth.register_history(200, n_procs=3, seed=3)
        wgl.check_batch([encode(models.cas_register(), hist)])
        assert fresh.cache_stats["wgl"]["misses"] == 2
        c = telemetry.get().counters()
        assert c["profiler.wgl.compile.hit"] == 1
        assert c["profiler.wgl.compile.miss"] == 2
        # only the hit launch skips the compile phase
        hits = [r for r in fresh.records()
                if r["kernel"] == "wgl" and "compile_ns" not in r]
        assert len(hits) == 1

    def test_scc_launch_record(self, fresh):
        rng = np.random.default_rng(0)
        n, e = 2000, 25_000  # past DEVICE_MIN_EDGES
        labels = scc.scc(n, rng.integers(0, n, e),
                         rng.integers(0, n, e), device=True)
        assert labels is not None and len(labels) == n
        recs = [r for r in fresh.records() if r["kernel"] == "scc"]
        assert recs
        r = recs[0]
        assert r["nodes"] == n and r["edges"] == e
        assert r.get("compute_ns", 0) > 0

    def test_elle_launch_record(self, fresh):
        from jepsen_tpu.tpu import elle

        hist = synth.list_append_history(600, seed=3)
        res = elle.check_list_append(hist, {"engine": "device"})
        assert res["valid?"] is True
        recs = [r for r in fresh.records()
                if r["kernel"] == "elle-append"]
        assert recs
        r = recs[0]
        assert r["txns"] > 0 and r["edges"] > 0
        assert r["encode_ns"] > 0  # host flatten/edge inference
        assert r["compute_ns"] > 0  # cycle detection

    def test_sharded_launch_attribution(self, fresh):
        from jepsen_tpu.tpu import ensemble

        hists = [synth.register_history(24, n_procs=3, seed=i)
                 for i in range(4)]
        encs = [encode(models.cas_register(), h) for h in hists]
        mesh = ensemble.default_mesh(1)
        res = ensemble.check_batch_sharded(encs, mesh=mesh, W=16, F=16)
        assert all(int(r) == wgl.VALID for r in res)
        recs = [r for r in fresh.records()
                if r["kernel"] == "wgl-sharded"]
        assert recs
        r = recs[0]
        assert r["devices"] == 1
        assert len(r["device_entries"]) == 1
        assert r["device_entries"][0] > 0
        assert r["balance"] == 1.0  # one device is trivially balanced

    def test_launch_records_land_in_telemetry_and_trace(self, fresh):
        from jepsen_tpu.reports import trace as rtrace

        _launch_small()
        spans = telemetry.get().events()
        kernel_spans = [s for s in spans
                        if s["name"].startswith("kernel:")]
        assert kernel_spans and kernel_spans[0]["attrs"]["rows"]
        doc = rtrace.chrome_trace({}, [], spans)
        rtrace.validate_chrome_trace(doc)
        dev = [e for e in doc["traceEvents"]
               if e.get("pid") == rtrace._PID_DEVICE
               and e.get("ph") == "X"]
        assert dev, "no device-track launch slices"
        assert dev[0]["name"] == "wgl"
        # kernel spans moved off the harness flame onto the device track
        harness = [e for e in doc["traceEvents"]
                   if e.get("pid") == rtrace._PID_HARNESS
                   and str(e.get("name", "")).startswith("kernel:")]
        assert not harness

    @pytest.mark.parametrize("site", ["wgl", "wgl-sharded", "scc"])
    def test_fresh_bucket_launch_never_lowers_again(self, fresh,
                                                    monkeypatch, site):
        """A bucket's first launch compiles once, through the jit
        dispatch; the profiler lowers nothing of its own."""
        from jepsen_tpu.tpu import ensemble

        lowered = []

        class CountLower:
            def __init__(self, fn):
                self.fn = fn

            def __call__(self, *a, **kw):
                return self.fn(*a, **kw)

            def lower(self, *a, **kw):
                lowered.append(site)
                return self.fn.lower(*a, **kw)

        def counted(mod, name):
            real = getattr(mod, name)
            monkeypatch.setattr(
                mod, name, lambda *a, **kw: CountLower(real(*a, **kw)))

        monkeypatch.setenv("JEPSEN_TPU_SPMD", "0")
        if site == "wgl":
            counted(wgl, "_jitted_kernel")
            assert int(_launch_small()[0]) == wgl.VALID
        elif site == "wgl-sharded":
            counted(ensemble, "_jitted_sharded")
            encs = [encode(models.cas_register(),
                           synth.register_history(24, n_procs=3, seed=i))
                    for i in range(2)]
            res = ensemble.check_batch_sharded(
                encs, mesh=ensemble.default_mesh(1), W=16, F=16)
            assert all(int(r) == wgl.VALID for r in res)
        else:
            counted(scc, "_jitted_scc")
            rng = np.random.default_rng(0)
            n, e = 2000, 25_000
            fresh.bucket_unclaim("scc", ("scc", scc._next_pow2(n + 1),
                                         scc._edge_pad(e)))
            assert scc.scc_device(n, rng.integers(0, n, e),
                                  rng.integers(0, n, e)) is not None
        assert fresh.cache_stats[site]["misses"] == 1
        recs = [r for r in fresh.records() if r["kernel"] == site]
        assert recs and recs[0]["compile_ns"] > 0
        assert lowered == []

    def test_metrics_json_schema_validates(self, fresh, tmp_path):
        _launch_small()
        _trace, mpath = telemetry.save(tmp_path)
        with open(mpath) as f:
            metrics = json.load(f)
        assert telemetry.validate_metrics(metrics) > 0

    def test_validate_metrics_rejects_bad_docs(self):
        with pytest.raises(ValueError):
            telemetry.validate_metrics({"spans": {}, "counters": {}})
        with pytest.raises(ValueError):
            telemetry.validate_metrics(
                {"spans": {"x": {"count": 1, "total_ns": -5,
                                 "max_ns": 0}},
                 "counters": {}, "gauges": {}})
        with pytest.raises(ValueError):
            telemetry.validate_metrics(
                {"spans": {}, "counters": {"c": "nope"}, "gauges": {}})
        assert telemetry.validate_metrics(
            {"spans": {"x": {"count": 2, "total_ns": 10,
                             "max_ns": 7}},
             "counters": {"c": 3}, "gauges": {"g": 1.5}}) == 3


class TestRecorderGuards:
    def test_straggler_record_dropped_after_reset(self, fresh):
        """A record opened before telemetry.reset() (the next run
        starting) is dropped at finish: its clock origin is stale."""
        rec = fresh.begin("wgl", bucket=("b",))
        telemetry.reset()
        fresh.finish(rec)
        assert fresh.records() == []
        assert not [s for s in telemetry.get()._spans
                    if s["name"].startswith("kernel:")]
        assert "profiler.wgl.launches" not in telemetry.get().counters()

    def test_record_span_epoch_guard(self):
        telemetry.reset()
        e = telemetry.get().epoch
        assert telemetry.record_span("kernel:w", 0, 5, epoch=e)
        telemetry.reset()
        assert telemetry.record_span("kernel:w", 0, 5, epoch=e) is None

    def test_disabled_profiler_is_noop(self):
        telemetry.reset()
        p = profiler.Profiler(enabled=False)
        rec = p.begin("wgl")
        p.cache_event("wgl", True)
        p.record_host("pack", 100, entries=5)
        p.finish(rec)
        assert p.records() == [] and p.cache_stats == {}
        assert not [c for c in telemetry.get().counters()
                    if c.startswith("profiler.")]

    def test_span_mirror_cap_counts_drops(self, fresh, monkeypatch):
        """The telemetry-mirror cap is configurable and never silent:
        launches past it count profiler.<k>.spans_dropped instead of
        vanishing (ISSUE-7 no-silent-caps satellite)."""
        monkeypatch.setenv("JEPSEN_TPU_PROFILE_MAX_SPANS", "3")
        p = profiler.get()
        for _ in range(5):
            p.finish(p.begin("wgl"))
        spans = [s for s in telemetry.get().events()
                 if s["name"] == "kernel:wgl"]
        counters = telemetry.get().counters()
        assert len(spans) == 3
        assert counters["profiler.wgl.spans_dropped"] == 2
        # aggregates still saw every launch
        assert counters["profiler.wgl.launches"] == 5

    def test_span_mirror_cap_default_unchanged(self, fresh):
        assert profiler.max_mirrored_launches() == \
            profiler.MAX_MIRRORED_LAUNCHES

    def test_bucket_unclaim_re_fresh(self, fresh):
        """A failed first launch releases its bucket claim, so the
        retry's real recompile records a miss, not a phantom hit."""
        assert fresh.bucket_fresh("scc", ("x",)) is True
        fresh.bucket_unclaim("scc", ("x",))
        assert fresh.bucket_fresh("scc", ("x",)) is True
        assert fresh.cache_stats["scc"] == {"hits": 0, "misses": 2}

    def test_scc_failure_unclaims_bucket(self, fresh, monkeypatch):
        """Site-level: an scc device launch that dies keeps the bucket
        fresh for the retry (the wgl._timed_launch discard analog).
        Single-device path pinned via JEPSEN_TPU_SPMD=0 (the sharded
        factory is a separate bucket family)."""
        def boom(*a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: boom")

        monkeypatch.setenv("JEPSEN_TPU_SPMD", "0")
        monkeypatch.setattr(scc, "_jitted_scc", lambda *a, **k: boom)
        rng = np.random.default_rng(0)
        n, e = 2000, 25_000
        # _seen_buckets persists for the process (it mirrors the XLA
        # cache); unclaim so the bucket is fresh whatever ran before
        fresh.bucket_unclaim("scc", ("scc", scc._next_pow2(n + 1),
                                     scc._edge_pad(e)))
        with pytest.raises(RuntimeError):
            scc.scc_device(n, rng.integers(0, n, e),
                           rng.integers(0, n, e))
        assert fresh.cache_stats["scc"] == {"hits": 0, "misses": 1}
        # the claim was released: the same shape is fresh (miss) again
        n_pad, e_pad = scc._next_pow2(n + 1), scc._edge_pad(e)
        assert fresh.bucket_fresh("scc", ("scc", n_pad, e_pad)) is True

    def test_pending_overflow_finalizes_every_stray(self, fresh):
        """The parking-lot cap aggregates ALL strays, dropping none."""
        objs = [object() for _ in range(257)]
        for o in objs:
            fresh.attach(o, fresh.begin("wgl"))
        fresh.attach(object(), fresh.begin("wgl"))  # trips the sweep
        assert len(fresh.records()) == 257
        c = telemetry.get().counters()
        assert c["profiler.wgl.launches"] == 257


class TestProfileReport:
    def _metrics(self, fresh):
        _launch_small()
        return telemetry.get().metrics()

    def test_kernel_table(self, fresh):
        m = self._metrics(fresh)
        rows = rprofile.kernel_rows(m)
        by_kernel = {r["kernel"]: r for r in rows}
        assert "wgl" in by_kernel and "encode" in by_kernel
        w = by_kernel["wgl"]
        assert w["launches"] == 1
        assert w["cache"] == "0/1"
        assert w["compile"] != "-" and w["wall"] != "-"
        assert "compute" in w["split"]
        text = rprofile.profile_text(telemetry.get().events(), m)
        assert "cache hit" in text and "wgl" in text
        assert "Slowest launches" in text
        html = rprofile.profile_html(m)
        assert "kernel profile" in html and "wgl" in html

    def test_profile_cli(self, fresh, tmp_path, capsys):
        from jepsen_tpu import cli

        _launch_small()
        telemetry.save(tmp_path)
        cmd = cli.profile_cmd()["profile"]
        p = argparse.ArgumentParser()
        cmd["parser_fn"](p)
        rc = cmd["run"](p.parse_args([str(tmp_path)]))
        out = capsys.readouterr().out
        assert rc == 0
        assert "cache hit" in out and "wall split" in out and "wgl" in out

    def test_empty_profile(self):
        assert "no kernel launches" in rprofile.profile_text([], {})
        assert rprofile.profile_html({}) == ""


class TestPrometheus:
    def test_text_scrape_parses(self, fresh):
        _launch_small()
        m = telemetry.get().metrics()
        text = rprofile.prometheus_text(m, run="reg/20260803")
        n = rprofile.validate_prometheus_text(text)
        assert n > 5
        assert "jepsen_tpu_profiler_wgl_launches" in text

    def test_endpoint_scrape_parses(self, fresh, tmp_path):
        from jepsen_tpu import web

        _launch_small()
        run = tmp_path / "reg" / "t1"
        run.mkdir(parents=True)
        telemetry.save(run)
        server = web.serve("127.0.0.1", 0, base=tmp_path)
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics?run=reg/t1",
                    timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain")
                body = resp.read().decode()
        finally:
            server.shutdown()
        assert rprofile.validate_prometheus_text(body) > 0
        assert 'run="reg/t1"' in body

    def test_endpoint_404_without_metrics(self, tmp_path):
        from jepsen_tpu import web

        server = web.serve("127.0.0.1", 0, base=tmp_path)
        try:
            port = server.server_address[1]
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics?run=nope",
                    timeout=10)
            assert ei.value.code == 404
        finally:
            server.shutdown()


class TestScalingAttribution:
    def test_parallel_efficiency(self):
        eff = profiler.parallel_efficiency(
            {1: 8.0, 2: 4.0, 4: 2.0, 8: 1.0})
        assert eff == {1: 1.0, 2: 1.0, 4: 1.0, 8: 1.0}
        flat = profiler.parallel_efficiency(
            {1: 3.77, 2: 3.43, 4: 3.29, 8: 3.43})
        assert flat[8] < 0.2  # a flat sweep
        assert profiler.parallel_efficiency({2: 1.0}) == {}

    def test_check_efficiency_warns_below_floor(self):
        msgs = []
        bad = profiler.check_efficiency(
            {1: 1.0, 2: 0.9, 4: 0.3, 8: 0.14}, log=msgs.append)
        assert [n for n, _e in bad] == [4, 8]
        assert len(msgs) == 2 and "4 devices" in msgs[0]
        assert profiler.check_efficiency({1: 1.0, 8: 0.9},
                                         log=msgs.append) == []

    def test_work_balance(self):
        # the sharded launches' load-balance figure (the contiguous
        # device_work helper died with the blocked shard layout —
        # ensemble.shard_layout attributes work per device now)
        assert profiler.work_balance([40, 40, 40, 40]) == 1.0
        assert profiler.work_balance([80, 40]) == 0.75
        assert profiler.work_balance([]) is None
        assert profiler.work_balance([0, 0]) is None


class TestTelemetryFilters:
    def _spans(self):
        return [
            {"id": 1, "parent": None, "name": "run", "t0": 0,
             "t1": 100_000_000},
            {"id": 2, "parent": 1, "name": "analyze", "t0": 0,
             "t1": 90_000_000},
            {"id": 3, "parent": 2, "name": "kernel:wgl", "t0": 0,
             "t1": 50_000_000},
            {"id": 4, "parent": 2, "name": "tiny", "t0": 0,
             "t1": 10_000},
            {"id": 5, "parent": None, "name": "open-span", "t0": 0},
        ]

    def test_min_ms_keeps_ancestors_and_open_spans(self):
        kept = rtel.filter_spans(self._spans(), min_ms=1.0)
        names = {e["name"] for e in kept}
        assert names == {"run", "analyze", "kernel:wgl", "open-span"}

    def test_top_keeps_n_longest_plus_ancestors(self):
        kept = rtel.filter_spans(self._spans(), top=1)
        names = {e["name"] for e in kept}
        # longest closed span is "run"; the open span always survives
        assert names == {"run", "open-span"}

    def test_no_filter_is_identity(self):
        spans = self._spans()
        assert rtel.filter_spans(spans) == spans

    def test_telemetry_text_reports_filtering(self):
        out = rtel.telemetry_text(self._spans(), None, min_ms=1.0)
        assert "filtered: showing" in out
        assert "tiny" not in out

    def test_cli_flags(self, tmp_path, capsys):
        from jepsen_tpu import cli

        util.init_relative_time()
        telemetry.reset()
        with telemetry.span("phase"):
            pass
        telemetry.save(tmp_path)
        cmd = cli.telemetry_cmd()["telemetry"]
        p = argparse.ArgumentParser()
        cmd["parser_fn"](p)
        rc = cmd["run"](p.parse_args(
            [str(tmp_path), "--min-ms", "0.0001", "--top", "5"]))
        assert rc == 0
        assert "filtered" in capsys.readouterr().out
