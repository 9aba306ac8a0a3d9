"""Verdict certificates + the search explorer (ISSUE 10).

The rejection matrix pins the validator's whole point: a tampered
linearization order, a forged cycle edge, and a stale certificate
replayed against an edited history must all fail loudly, while
device- and host-derived certificates for the same seeded histories
must both validate and agree. The explorer half pins the kernel's
search-dynamics outputs (per-level frontier occupancy, states,
dedup hits, witness position) through the profiler, the profile CLI
columns, the Perfetto counter track, and the web panel."""

import copy
import json

import pytest

from jepsen_tpu import checker, core, store, telemetry, testing
from jepsen_tpu import generator as gen
from jepsen_tpu.checker import models
from jepsen_tpu.history import History, op
from jepsen_tpu.tpu import certify, elle, synth, wgl
from jepsen_tpu.tpu.encode import encode


def _register_hist(n=200, seed=3, crash_p=0.1):
    return synth.register_history(n, n_procs=4, seed=seed,
                                  crash_p=crash_p)


def _invalid_hist(n=1500, seed=5, at=0.6):
    h, _bad = synth.corrupt_register_history(
        synth.register_history(n, n_procs=4, seed=seed), at_frac=at)
    return h


def _cyclic_append_hist():
    """A two-txn ww cycle (G0) witnessed by a third txn's reads."""
    ops = []

    def txn(p, mops, ok_mops=None):
        ops.append(op(index=len(ops), time=len(ops), type="invoke",
                      process=p, f="txn", value=mops))
        ops.append(op(index=len(ops), time=len(ops), type="ok",
                      process=p, f="txn", value=ok_mops or mops))

    txn(0, [["append", "x", 1], ["append", "y", 2]])
    txn(1, [["append", "x", 2], ["append", "y", 1]])
    txn(2, [["r", "x", None], ["r", "y", None]],
        [["r", "x", [1, 2]], ["r", "y", [1, 2]]])
    return History(ops)


class TestSchema:
    def test_absent_is_schema_valid(self):
        certify.validate_schema(certify.absent("host floor"))

    def test_absent_requires_reason(self):
        with pytest.raises(certify.CertificateError):
            certify.validate_schema({"v": 1, "absent": ""})

    def test_unknown_version_rejected(self):
        with pytest.raises(certify.CertificateError):
            certify.validate_schema({"v": 99, "kind": "wgl"})

    def test_full_cert_schema(self):
        h = _register_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        certify.validate_schema(out["certificate"])

    def test_absent_validate_raises(self):
        with pytest.raises(certify.CertificateError):
            certify.validate(History([]), certify.absent("nope"))


class TestWglValid:
    def test_valid_certificate_validates(self):
        h = _register_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        assert out["valid?"] is True
        assert "absent" not in out["certificate"]
        certify.validate(h, out["certificate"])

    def test_segmented_certificate_composes(self):
        h = synth.register_history(6000, n_procs=4, seed=7)
        out = wgl.analysis(models.cas_register(), h, certify=True)
        assert out["analyzer"] == "tpu-segmented"
        cert = out["certificate"]
        assert len(cert["segments"]) > 1  # really per-segment
        certify.validate(h, cert)

    def test_tampered_order_rejected(self):
        h = _register_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        cert = copy.deepcopy(out["certificate"])
        order = cert["segments"][0]["order"]
        order[0], order[-1] = order[-1], order[0]
        with pytest.raises(certify.CertificateError):
            certify.validate(h, cert)

    def test_dropped_op_rejected(self):
        """A 'proof' that simply omits a completed op is not a
        whole-history proof."""
        h = _register_hist(crash_p=0.0)
        out = wgl.analysis(models.cas_register(), h, certify=True)
        cert = copy.deepcopy(out["certificate"])
        cert["segments"][0]["order"].pop()
        with pytest.raises(certify.CertificateError,
                           match="omits"):
            certify.validate(h, cert)

    def test_discarding_completed_op_rejected(self):
        h = _register_hist(crash_p=0.0)
        out = wgl.analysis(models.cas_register(), h, certify=True)
        cert = copy.deepcopy(out["certificate"])
        cert["segments"][0]["order"][0][1] = "discard"
        with pytest.raises(certify.CertificateError):
            certify.validate(h, cert)

    def test_stale_certificate_rejected(self):
        h = _register_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        edited = History(list(h)[:-2], assign_indices=False)
        with pytest.raises(certify.CertificateError, match="stale"):
            certify.validate(edited, out["certificate"])


class TestWglInvalid:
    def test_witness_certificate_validates(self):
        h = _invalid_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        assert out["valid?"] is False
        cert = out["certificate"]
        assert "absent" not in cert, cert
        certify.validate(h, cert)

    def test_segmented_witness_validates(self):
        h = _invalid_hist(n=6000, seed=11, at=0.5)
        out = wgl.analysis(models.cas_register(), h, certify=True)
        assert out["valid?"] is False
        cert = out["certificate"]
        assert cert["segments"], "pre-witness segments certified too"
        certify.validate(h, cert)

    def test_tampered_witness_state_rejected(self):
        h = _invalid_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        cert = copy.deepcopy(out["certificate"])
        cert["witness"]["state"] = 999_999
        with pytest.raises(certify.CertificateError):
            certify.validate(h, cert)

    def test_unstuck_witness_rejected(self):
        """Claiming an op is stuck when it actually applies must
        fail — the validator re-steps the model itself."""
        h = _register_hist(crash_p=0.0)  # valid history
        out = wgl.analysis(models.cas_register(), h, certify=True)
        good = out["certificate"]
        # forge an 'invalid' certificate out of the valid proof: the
        # prefix replays fine, but the claimed stuck op applies
        order = good["segments"][0]["order"]
        forged = {
            "v": 1, "kind": "wgl", "verdict": "invalid",
            "model": good["model"], "history": good["history"],
            "segments": [],
            "witness": {"op-index": order[-1][0],
                        "prefix": order[:-1],
                        "pending": [order[-1][0]]},
        }
        with pytest.raises(certify.CertificateError):
            certify.validate(h, forged)

    def test_witness_position_attached(self):
        h = _invalid_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        s = out["search"]
        assert 0.0 <= s["witness-position"] <= 1.0
        assert s["witness-entry"] < s["entries"]


class TestDeviceHostEquivalence:
    @pytest.mark.parametrize("seed", [21, 22, 23])
    def test_certificates_agree_on_seeded_histories(self, seed):
        """The device kernel's verdict+proof and the host search's
        must agree and both validate — the parity invariant the
        certificate layer turns into a per-run check."""
        h = synth.register_history(300, n_procs=4, seed=seed,
                                   crash_p=0.15)
        m = models.cas_register()
        dev = wgl.analysis(m, h, algorithm="tpu", certify=True)
        host = wgl.analysis(m, h, algorithm="wgl", certify=True)
        assert dev["valid?"] == host["valid?"]
        for out in (dev, host):
            assert "absent" not in out["certificate"]
            certify.validate(h, out["certificate"])

    def test_invalid_agrees_too(self):
        h = _invalid_hist(n=900, seed=31, at=0.4)
        m = models.cas_register()
        dev = wgl.analysis(m, h, algorithm="tpu", certify=True)
        host = wgl.analysis(m, h, algorithm="wgl", certify=True)
        assert dev["valid?"] is False and host["valid?"] is False
        certify.validate(h, dev["certificate"])
        certify.validate(h, host["certificate"])


class TestElleCertificates:
    def test_valid_append_certificate(self):
        h = synth.list_append_history(300, seed=4)
        res = elle.check_list_append(h, {"certify": True})
        assert res["valid?"] is True
        certify.validate(h, res["certificate"])

    def test_valid_rw_certificate(self):
        h = synth.rw_register_history(300, seed=9)
        res = elle.check_rw_register(h, {"certify": True})
        assert res["valid?"] is True
        certify.validate(h, res["certificate"])

    def test_cycle_certificate_validates(self):
        h = _cyclic_append_hist()
        res = elle.check_list_append(h, {"certify": True})
        assert res["valid?"] is False
        cert = res["certificate"]
        assert cert["cycle"], cert
        certify.validate(h, cert)

    def test_forged_cycle_edge_rejected(self):
        h = _cyclic_append_hist()
        res = elle.check_list_append(h, {"certify": True})
        cert = copy.deepcopy(res["certificate"])
        cert["cycle"][0]["value"] = 777
        with pytest.raises(certify.CertificateError, match="forged"):
            certify.validate(h, cert)

    def test_broken_cycle_chain_rejected(self):
        h = _cyclic_append_hist()
        res = elle.check_list_append(h, {"certify": True})
        cert = copy.deepcopy(res["certificate"])
        cert["cycle"][0]["to"] = cert["cycle"][0]["from"]
        with pytest.raises(certify.CertificateError):
            certify.validate(h, cert)

    def test_tampered_topo_order_rejected(self):
        ops = []
        ops.append(op(index=0, time=0, type="invoke", process=0,
                      f="txn", value=[["append", "x", 1]]))
        ops.append(op(index=1, time=1, type="ok", process=0,
                      f="txn", value=[["append", "x", 1]]))
        ops.append(op(index=2, time=2, type="invoke", process=1,
                      f="txn", value=[["r", "x", None]]))
        ops.append(op(index=3, time=3, type="ok", process=1,
                      f="txn", value=[["r", "x", [1]]]))
        h = History(ops)
        res = elle.check_list_append(h, {"certify": True})
        assert res["valid?"] is True
        cert = copy.deepcopy(res["certificate"])
        cert["topo-order"] = list(reversed(cert["topo-order"]))
        with pytest.raises(certify.CertificateError):
            certify.validate(h, cert)

    def test_g1a_certificate(self):
        ops = []
        ops.append(op(index=0, time=0, type="invoke", process=0,
                      f="txn", value=[["append", "x", 1]]))
        ops.append(op(index=1, time=1, type="fail", process=0,
                      f="txn", value=[["append", "x", 1]]))
        ops.append(op(index=2, time=2, type="invoke", process=1,
                      f="txn", value=[["r", "x", None]]))
        ops.append(op(index=3, time=3, type="ok", process=1,
                      f="txn", value=[["r", "x", [1]]]))
        h = History(ops)
        res = elle.check_list_append(h, {"certify": True})
        assert res["valid?"] is False
        cert = res["certificate"]
        if "absent" not in cert:
            assert cert.get("anomaly", {}).get("class") == "G1a"
            certify.validate(h, cert)
            bad = copy.deepcopy(cert)
            bad["anomaly"]["value"] = 42
            with pytest.raises(certify.CertificateError):
                certify.validate(h, bad)

    def test_search_stats_attached(self):
        h = synth.list_append_history(300, seed=4)
        res = elle.check_list_append(h)
        s = res["search"]
        assert s["edges"] == res["edge-count"]
        assert s["per-key-edges"]
        assert s["keys"] >= len(s["per-key-edges"])


class TestStampResults:
    def test_stamp_marks_certified(self):
        h = _register_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        results = {"workload": out, "valid?": out["valid?"]}
        counts = certify.stamp_results(results, h)
        assert counts == {"certified": 1, "errors": 0, "absent": 0}
        assert results["workload"]["certified"] is True

    def test_stamp_marks_error_on_tamper(self):
        h = _register_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        order = out["certificate"]["segments"][0]["order"]
        order[0], order[-1] = order[-1], order[0]
        results = {"workload": out}
        counts = certify.stamp_results(results, h)
        assert counts["errors"] == 1
        assert "certificate-error" in results["workload"]

    def test_stamp_counts_absent(self):
        results = {"w": {"valid?": True,
                         "certificate": certify.absent("host floor")}}
        counts = certify.stamp_results(results, History([]))
        assert counts == {"certified": 0, "errors": 0, "absent": 1}
        assert "certified" not in results["w"]

    def test_disabled_extraction_is_honestly_absent(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_CERTIFY", "0")
        h = _register_hist()
        out = wgl.analysis(models.cas_register(), h, certify=True)
        assert "absent" in out["certificate"]


class TestIndependentKeys:
    def test_per_key_certificates_validate_against_full_history(self):
        from jepsen_tpu import independent

        ops = []
        t = [0]

        def add(p, f, v, typ="invoke"):
            ops.append(op(index=len(ops), time=t[0], type=typ,
                          process=p, f=f, value=v))
            t[0] += 1

        for k in ("a", "b"):
            add(0, "write", (k, 1))
            add(0, "write", (k, 1), "ok")
            add(1, "read", (k, None))
            add(1, "read", (k, 1), "ok")
        h = History(ops, assign_indices=False)
        inner = checker.linearizable({"model": models.register()})
        res = independent.checker(inner).check({}, h, {})
        assert res["valid?"] is True
        for k, r in res["results"].items():
            cert = r["certificate"]
            assert cert["key"] == k
            certify.validate(h, cert)
        counts = certify.stamp_results(res, h)
        assert counts["certified"] == 2 and counts["errors"] == 0


class TestSearchExplorer:
    def test_kernel_reports_search_shape(self):
        from jepsen_tpu.tpu import profiler

        telemetry.reset()
        profiler.reset()
        m = models.register(0)
        encs = [encode(m, _register_hist(80, seed=s))
                for s in range(41, 45)]
        res = wgl.check_batch(encs)
        assert set(res) <= {wgl.VALID, wgl.INVALID, wgl.UNKNOWN}
        c = telemetry.get().counters()
        assert c["profiler.wgl.iterations"] >= 1
        assert c["profiler.wgl.states"] >= 1
        g = telemetry.get().gauges()
        assert g["profiler.wgl.frontier_peak"] >= 1
        recs = [r for r in profiler.get().records()
                if r["kernel"] == "wgl"]
        assert recs
        r = recs[-1]
        assert r["frontier_peak"] >= 1
        assert r["states_explored"] >= 1
        assert isinstance(r["frontier_curve"], list)
        assert len(r["frontier_curve"]) <= 32

    def test_profile_table_has_explorer_columns(self):
        from jepsen_tpu.reports import profile as rprofile

        metrics = {"counters": {
            "profiler.wgl.launches": 3,
            "profiler.wgl.states": 1200,
            "profiler.wgl.dedup_hits": 30,
        }, "gauges": {"profiler.wgl.frontier_peak": 64}}
        text = rprofile.profile_text([], metrics)
        assert "frontier" in text and "dedup" in text
        rows = {r["kernel"]: r for r in rprofile.kernel_rows(metrics)}
        assert rows["wgl"]["frontier"] == "64"
        assert rows["wgl"]["states"] == "1.2k"

    def test_trace_gains_frontier_counter_track(self):
        from jepsen_tpu.reports import trace as rtrace

        spans = [{"name": "kernel:wgl", "t0": 1000, "t1": 9000,
                  "thread": "t", "attrs": {
                      "frontier_curve": [1, 4, 9, 4, 1],
                      "frontier_peak": 9}}]
        doc = rtrace.chrome_trace({}, [], spans)
        counters = [e for e in doc["traceEvents"]
                    if e.get("ph") == "C"
                    and e["name"] == "wgl frontier"]
        assert len(counters) == 5
        assert counters[2]["args"]["frontier"] == 9.0
        rtrace.validate_chrome_trace(doc)

    def test_web_explorer_panel(self, tmp_path):
        from jepsen_tpu import web

        d = tmp_path / "demo" / "t1"
        d.mkdir(parents=True)
        (d / "telemetry.jsonl").write_text(json.dumps(
            {"name": "kernel:wgl", "t0": 0, "t1": 100,
             "thread": "t",
             "attrs": {"frontier_curve": [1, 5, 2],
                       "frontier_peak": 5, "iterations": 3,
                       "states_explored": 8}}) + "\n")
        h = _invalid_hist(n=400, seed=5, at=0.5)
        out = wgl.analysis(models.cas_register(), h, certify=True)
        (d / "results.json").write_text(json.dumps(
            {"workload": {"valid?": False,
                          "search": out["search"],
                          "certificate": out["certificate"],
                          "certified": True}}, default=repr))
        html = web._explorer_html(d, "demo/t1")
        assert "search explorer" in html
        assert "polyline" in html                 # the sparkline
        assert "witnessed at" in html             # the marker
        assert "certified" in html


class TestCoverageWitnessPosition:
    def test_witness_frac_folds_into_atlas_cells(self):
        from jepsen_tpu import coverage

        results = {"valid?": False, "workload": {
            "valid?": False,
            "anomaly-classes": {"nonlinearizable": "witnessed"},
            "op-indices": [3],
            "search": {"witness-position": 0.12, "witness-entry": 3,
                       "entries": 25}}}
        test = {"name": "wf", "history": [], "results": results,
                "spec": {"workload": "register", "opts": {}}}
        rec = coverage.build_record(test,
                                    recorder=coverage.Recorder())
        coverage.validate_record(rec)
        [a] = [a for a in rec["anomalies"]
               if a["class"] == "nonlinearizable"]
        assert a["witness-frac"] == 0.12
        entry = coverage.atlas_entry(rec)
        coverage.validate_atlas([entry])
        assert entry["witness-frac"] == {"nonlinearizable": 0.12}
        cells = coverage.aggregate([entry])
        cell = cells[("none", "register", "nonlinearizable")]
        assert cell["earliest-witness-frac"] == 0.12
        # the witnessed detail names the localization percentile
        text = coverage.coverage_text(cells, ["register"])
        assert "earliest witness at 12%" in text

    def test_bad_witness_frac_rejected(self):
        from jepsen_tpu import coverage

        rec = {"schema": 1, "run": "r", "ts": 1.0, "workload": "w",
               "faults": [], "valid": False,
               "anomalies": [{"class": "x", "checker": "c",
                              "outcome": "witnessed",
                              "witness-frac": 7.0}]}
        with pytest.raises(ValueError):
            coverage.validate_record(rec)


class TestSeededRunArtifacts:
    """The tier-1 acceptance invariant: a seeded end-to-end run's
    results carry schema-valid certificates that independently
    re-validate from the stored artifacts — and the certify CLI
    agrees."""

    def _run(self, tmp_path):
        state = testing.AtomState()
        test = testing.noop_test()
        test.update(
            name="certify-e2e", store_base=str(tmp_path),
            nodes=["n1", "n2"], concurrency=2,
            db=testing.AtomDB(state),
            client=testing.AtomClient(state, latency_s=0.0),
            checker=checker.compose({
                "linear": checker.linearizable(
                    {"model": models.cas_register(0)}),
                "stats": checker.stats()}),
            generator=gen.clients(gen.limit(40,
                                            lambda: {"f": "read"})))
        return core.run(test)

    def test_run_certificate_roundtrip(self, tmp_path):
        t = self._run(tmp_path)
        res = t["results"]
        assert res["linear"]["certified"] is True
        d = store.path(t)
        with open(d / "results.json") as f:
            loaded = json.load(f)
        cert = loaded["linear"]["certificate"]
        certify.validate_schema(cert)
        from jepsen_tpu.store import format as fmt

        hist = fmt.read_history(d / "history.jlog")
        certify.validate(hist, cert)

    def test_offline_analyze_restamps_certificates(self, tmp_path):
        """`analyze --resume` re-enters core.analyze, so offline
        re-analysis re-extracts AND re-validates proofs against the
        recovered history (the crash-recovery story keeps the proof
        plane)."""
        from jepsen_tpu import resume

        state = testing.AtomState()
        test = testing.noop_test()
        test.update(
            name="certify-offline", store_base=str(tmp_path),
            nodes=["n1", "n2"], concurrency=2,
            db=testing.AtomDB(state),
            client=testing.AtomClient(state, latency_s=0.0),
            checker=checker.compose({
                "linear": checker.linearizable(
                    {"model": models.cas_register(0)}),
                "stats": checker.stats()}),
            spec={"workload": "register", "opts": {}},
            generator=gen.clients(gen.limit(30,
                                            lambda: {"f": "read"})))
        t = core.run(test)
        d = store.path(t)

        def rebuild(opts):
            return {"checker": checker.compose({
                "linear": checker.linearizable(
                    {"model": models.cas_register(0)}),
                "stats": checker.stats()})}

        t2 = resume.analyze_run(d, resume=False, test_fn=rebuild)
        res = t2["results"]
        assert res["linear"]["certified"] is True
        assert res["analysis"]["certificates"]["certified"] >= 1
        assert res["analysis"]["certificates"]["errors"] == 0

    def test_certify_cli(self, tmp_path, capsys):
        import argparse

        from jepsen_tpu import cli as jcli

        t = self._run(tmp_path)
        d = store.path(t)
        cmd = jcli.certify_cmd()["certify"]
        ns = argparse.Namespace(test=str(d), timestamp="latest",
                                store=None, print_=False)
        assert cmd["run"](ns) == 0
        out = capsys.readouterr().out
        assert "certified" in out
        # tamper the stored certificate: the CLI must fail it
        with open(d / "results.json") as f:
            res = json.load(f)
        order = res["linear"]["certificate"]["segments"][0]["order"]
        if len(order) > 1:
            order[0], order[-1] = order[-1], order[0]
        else:
            order[0][0] += 1
        with open(d / "results.json", "w") as f:
            json.dump(res, f, default=repr)
        assert cmd["run"](ns) == 1
