"""Telemetry subsystem tests: span nesting (within and across
threads), counter/gauge aggregation, JSONL + metrics round-trips from
the store, the span-tree renderers, and the instrumented pipeline —
a clusterless run() must leave phase spans, interpreter counters, and
device-kernel profile values behind."""

import json
import random
import threading

from jepsen_tpu import checker, core, store, telemetry, testing
from jepsen_tpu import generator as gen
from jepsen_tpu.checker import models
from jepsen_tpu.reports import telemetry as rtel


class TestRecorder:
    def test_span_nesting_same_thread(self):
        t = telemetry.Telemetry()
        with t.span("outer"):
            with t.span("inner"):
                pass
        inner, outer = t.events()  # completion order
        assert (inner["name"], outer["name"]) == ("inner", "outer")
        assert inner["parent"] == outer["id"]
        assert outer["parent"] is None
        assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]

    def test_span_nesting_across_threads(self):
        """Each thread keeps its own span stack: spans opened on
        worker threads are roots (never children of another thread's
        open span), and their own children nest under them."""
        t = telemetry.Telemetry()
        ready = threading.Barrier(3)

        def worker(i):
            with t.span(f"w{i}"):
                with t.span(f"w{i}-child"):
                    ready.wait(timeout=5)

        with t.span("main"):
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(2)]
            for th in threads:
                th.start()
            ready.wait(timeout=5)  # all three spans open concurrently
            for th in threads:
                th.join()
        by_name = {e["name"]: e for e in t.events()}
        assert by_name["main"]["parent"] is None
        for i in range(2):
            assert by_name[f"w{i}"]["parent"] is None
            assert (by_name[f"w{i}-child"]["parent"]
                    == by_name[f"w{i}"]["id"])

    def test_counter_aggregation_across_threads(self):
        t = telemetry.Telemetry()

        def bump():
            for _ in range(1000):
                t.count("hits")
                t.count("bytes", 3)

        threads = [threading.Thread(target=bump) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert t.counters() == {"hits": 4000, "bytes": 12000}
        t.gauge("occupancy", 0.5)
        t.gauge("occupancy", 0.75)  # last write wins
        assert t.gauges() == {"occupancy": 0.75}
        t.gauge_max("largest", 50)
        t.gauge_max("largest", 2)   # max survives later smaller sets
        assert t.gauges()["largest"] == 50

    def test_metrics_aggregates_spans(self):
        t = telemetry.Telemetry()
        for _ in range(3):
            with t.span("x"):
                pass
        m = t.metrics()
        assert m["spans"]["x"]["count"] == 3
        assert m["spans"]["x"]["total_ns"] >= m["spans"]["x"]["max_ns"]

    def test_disabled_recorder_records_nothing(self):
        t = telemetry.Telemetry(enabled=False)
        with t.span("x"):
            t.count("c")
            t.gauge("g", 1)
        assert t.events() == []
        assert t.counters() == {} and t.gauges() == {}

    def test_reset_bumps_epoch(self):
        """Deferred flushers (interpreter workers) use the epoch to
        detect an intervening reset and drop stale tallies."""
        t = telemetry.Telemetry()
        e0 = t.epoch
        t.count("n")
        t.reset()
        assert t.epoch == e0 + 1
        assert t.counters() == {}
        # a span completing after an intervening reset is dropped too:
        # its id and clock origin belong to the previous run
        with t.span("stale"):
            t.reset()
        assert t.events() == []

    def test_timed_decorator(self):
        t = telemetry.Telemetry()

        @t.timed("f")
        def f(x):
            return x + 1

        assert f(1) == 2
        assert t.metrics()["spans"]["f"]["count"] == 1


class TestRoundTrip:
    def test_jsonl_and_metrics_roundtrip(self, tmp_path):
        t = telemetry.Telemetry()
        with t.span("a", phase="case"):
            with t.span("b"):
                pass
        t.count("n", 2)
        t.gauge("g", 1.5)
        trace, metrics = t.save(tmp_path)
        back = list(telemetry.read_events(trace))
        assert [e["name"] for e in back] == ["b", "a"]
        assert back[1]["attrs"] == {"phase": "case"}
        assert back[0]["parent"] == back[1]["id"]
        m = telemetry.read_metrics(metrics)
        assert m["counters"] == {"n": 2}
        assert m["gauges"] == {"g": 1.5}
        assert m["spans"]["a"]["count"] == 1

    def test_torn_trailing_line_dropped(self, tmp_path):
        t = telemetry.Telemetry()
        with t.span("a"):
            pass
        trace, _metrics = t.save(tmp_path)
        with open(trace, "a") as f:
            f.write('{"name": "torn')  # writer died mid-line
        assert [e["name"] for e in telemetry.read_events(trace)] == ["a"]

    def test_missing_artifacts(self, tmp_path):
        assert list(telemetry.read_events(tmp_path / "nope.jsonl")) == []
        assert telemetry.read_metrics(tmp_path / "nope.json") is None
        events, metrics = store.load_telemetry(tmp_path)
        assert events == [] and metrics is None


class TestRendering:
    def test_span_tree_lines(self):
        t = telemetry.Telemetry()
        with t.span("run"):
            with t.span("case"):
                pass
            with t.span("analyze"):
                pass
        lines = rtel.span_tree_lines(t.events())
        assert lines[0].startswith("run")
        assert lines[1].startswith("  case")
        assert lines[2].startswith("  analyze")

    def test_text_and_html_render(self):
        t = telemetry.Telemetry()
        with t.span("run"):
            t.count("profiler.wgl.compile_ns", 12_000_000)
            t.gauge("wgl.batch.occupancy", 0.5)
        text = rtel.telemetry_text(t.events(), t.metrics())
        assert "run" in text and "profiler.wgl.compile_ns" in text
        assert "12.0ms" in text  # _ns counters render as durations
        html = rtel.telemetry_html("demo", t.events(), t.metrics())
        assert "wgl.batch.occupancy" in html and "<table" in html


class TestPipeline:
    def test_clusterless_run_records_phases_and_artifacts(self, tmp_path):
        from jepsen_tpu.workloads import register as register_wl

        state = testing.AtomState()
        rng = random.Random(7)
        test = testing.noop_test()
        test.update(
            name="telemetry-e2e", store_base=str(tmp_path),
            nodes=["n1", "n2"], concurrency=4,
            client=testing.AtomClient(state),
            checker=checker.compose({
                "linear": checker.linearizable(
                    {"model": models.cas_register()}),
                "stats": checker.stats()}),
            generator=gen.clients(gen.limit(
                60, lambda: register_wl.cas_op_mix(rng, n_values=3))))
        test = core.run(test)
        assert test["results"]["valid?"] is True, test["results"]

        # the :telemetry summary rides in the results
        summ = test["results"]["telemetry"]
        for phase in ("run", "os-setup", "db-cycle", "case",
                      "snarf-logs", "teardown-db", "teardown-os",
                      "analyze"):
            assert phase in summ["phases"], (phase, summ["phases"])
        assert summ["phases"]["run"] >= summ["phases"]["case"] > 0
        # per-checker timings
        assert set(summ["checkers"]) >= {"linear", "stats"}
        c = summ["counters"]
        assert c["interpreter.dispatched"] == 60
        assert c.get("interpreter.ops.ok", 0) > 0
        assert c["interpreter.invoke_ns"] > 0
        # the linearizable checker went through the device kernel
        assert c.get("wgl.batch.histories", 0) >= 1
        assert c.get("profiler.wgl.launches", 0) >= 1
        assert c.get("profiler.wgl.iterations", 0) >= 1

        # artifacts land in the store directory and read back
        d = store.path(test)
        assert (d / "telemetry.jsonl").exists()
        assert (d / "metrics.json").exists()
        events, metrics = store.load_telemetry(d)
        names = {e["name"] for e in events}
        assert {"run", "case", "analyze", "checker:linear"} <= names
        assert (metrics["counters"]["interpreter.dispatched"]
                == c["interpreter.dispatched"])
        # results.json carries the summary too
        with open(d / "results.json") as f:
            saved = json.load(f)
        assert "telemetry" in saved

    def test_cli_telemetry_subcommand(self, tmp_path, capsys):
        import pytest

        from jepsen_tpu import cli

        state = testing.AtomState()
        test = testing.noop_test()
        test.update(
            name="telemetry-cli", store_base=str(tmp_path),
            nodes=["n1"], concurrency=2,
            client=testing.AtomClient(state),
            checker=checker.stats(),
            generator=gen.clients(gen.limit(10, lambda: {"f": "read"})))
        test = core.run(test)
        d = store.path(test)
        with pytest.raises(SystemExit) as e:
            cli.run_cli(cli.telemetry_cmd(), ["telemetry", str(d)])
        assert e.value.code == 0
        out = capsys.readouterr().out
        assert "# Spans" in out and "run" in out
        assert "interpreter.dispatched" in out

    def test_crashed_invokes_still_count_client_time(self):
        """A client that waits then raises must still contribute its
        wait to interpreter.invoke_ns — timeout-heavy runs would
        otherwise show near-zero client time next to a pile of
        worker-crashes."""
        import time as _t

        from jepsen_tpu import client as jclient
        from jepsen_tpu import interpreter, util

        class SlowCrash(jclient.Client):
            def open(self, test, node):
                return self

            def invoke(self, test, op):
                _t.sleep(0.02)
                raise RuntimeError("timeout")

        telemetry.reset()
        util.init_relative_time()
        t = testing.noop_test()
        t.update(concurrency=1, client=SlowCrash(),
                 generator=gen.on_threads({0}, gen.limit(
                     3, gen.repeat({"f": "w"}))))
        t = interpreter.run(dict(t))
        c = telemetry.get().counters()
        assert c["interpreter.worker-crashes"] == 3
        assert c["interpreter.invoke_ns"] >= 3 * 15_000_000

    def test_nemesis_spans_recorded(self):
        from jepsen_tpu import nemesis as jnemesis
        from jepsen_tpu.history import op

        telemetry.reset()
        nem = jnemesis.validate(jnemesis.noop).setup({})
        nem.invoke({}, op(type="info", process="nemesis", f="start"))
        names = [e["name"] for e in telemetry.get().events()]
        assert "nemesis:setup" in names and "nemesis:start" in names


class TestKernelMetrics:
    def test_batched_check_reports_kernel_profile(self):
        """A batched wgl check must leave nonzero compile-time,
        while-loop iteration, and batch-occupancy values behind."""
        from jepsen_tpu.checker import models as m2
        from jepsen_tpu.tpu import synth, wgl
        from jepsen_tpu.tpu.encode import encode

        telemetry.reset()
        model = m2.cas_register()
        encs = [encode(model, synth.register_history(
            120, n_procs=3, seed=50 + i)) for i in range(4)]
        # nonstandard W/F pin a fresh compile bucket even when earlier
        # tests in this process warmed the default 32/64 kernel
        res = wgl.check_batch(encs, W=20, F=24)
        assert (res == wgl.VALID).all()
        c = telemetry.get().counters()
        assert c["profiler.wgl.compile.miss"] >= 1
        assert c["profiler.wgl.compile_ns"] > 0
        assert c["profiler.wgl.launches"] >= 1
        assert c["profiler.wgl.iterations"] >= 1
        assert c["wgl.batch.histories"] == 4
        assert 0 < c["wgl.batch.entries"] <= c["wgl.batch.slots"]
        g = telemetry.get().gauges()
        assert 0 < g["wgl.batch.occupancy"] <= 1

    def test_scc_and_elle_counters(self):
        from jepsen_tpu.tpu import elle_device, synth

        telemetry.reset()
        hist = synth.list_append_history(300, seed=5)
        res = elle_device.check_list_append_device(hist, device=False)
        assert res["valid?"] is True
        c = telemetry.get().counters()
        assert c["elle.txns"] == res["txn-count"]
        assert c["elle.edges"] == res["edge-count"]
        assert c.get("scc.path.host", 0) >= 1  # small graph: host path
