"""Fleet suite (ISSUE 13): crash-safe multi-tenant
checking-as-a-service.

The robustness contract under test: no lost chunks, no wedged queues,
no verdict ever silently wrong or silently dropped — under chaos frame
loss, mid-stream server SIGKILL, and quota saturation. The acceptance
invariant (TestMultiTenantE2E / TestChaosFleet): N concurrent seeded
runs streamed through ONE server — including a kill+restart schedule
and a chaos-framed schedule — produce per-run verdicts and validating
certificates IDENTICAL to solo runs, with admission control rejecting
(never corrupting) the over-quota tenant.
"""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from jepsen_tpu import chaos, core, telemetry, testing, util
from jepsen_tpu import generator as gen
from jepsen_tpu.checker import models
from jepsen_tpu.fleet import client as fclient
from jepsen_tpu.fleet import flightrec
from jepsen_tpu.fleet import scheduler as fsched
from jepsen_tpu.fleet import server as fserver
from jepsen_tpu.fleet import wal as fwal
from jepsen_tpu.fleet import wire
from jepsen_tpu.reports import trace as rtrace
from jepsen_tpu.history import History, op as make_op
from jepsen_tpu.tpu import certify, synth, wgl

SEED = 4242


def seeded_hist(seed, n=300, corrupt=False):
    h = synth.register_history(n, seed=seed)
    if corrupt:
        h, _ = synth.corrupt_register_history(h)
    return h


def stream_run(addr, tenant, run, hist, chunk=50, transport=None,
               io_timeout_s=3.0, deadline_s=120.0):
    """Streams a history and returns the verdict envelope, retrying
    whole chunks across server restarts (what a polite tenant does
    with its retry-after budget)."""
    c = fclient.FleetClient(addr, tenant, run, model="cas-register",
                            transport=transport,
                            io_timeout_s=io_timeout_s)
    ops = list(hist)
    deadline = time.monotonic() + deadline_s
    i = 0
    while i < len(ops):
        try:
            c.send_chunk(ops[i:i + chunk])
            i += chunk
        except fclient.FleetError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.2)
    env = c.finish(timeout_s=deadline_s)
    c.close()
    return env


def solo_verdict(hist):
    return wgl.analysis(models.cas_register(), hist, certify=True)


def assert_verdict_matches_solo(hist, fleet_result, solo):
    """The acceptance comparison: same verdict, and the fleet's
    certificate independently validates against the raw history —
    for valid runs the proofs are bit-identical."""
    assert fleet_result["valid?"] == solo["valid?"]
    certify.validate(hist, fleet_result["certificate"])
    if solo["valid?"] is True:
        assert json.dumps(fwal.json_safe(solo["certificate"]),
                          sort_keys=True) == \
            json.dumps(fleet_result["certificate"], sort_keys=True)


# ---------------------------------------------------------------------------
# wire framing
# ---------------------------------------------------------------------------

class TestWire:
    def test_frame_round_trip(self):
        a, b = socket.socketpair()
        try:
            msg = {"type": "chunk", "seq": 3, "ops": [{"f": "read"}]}
            wire.send_msg(a, msg)
            assert wire.recv_msg(b) == msg
        finally:
            a.close()
            b.close()

    def test_corrupt_frame_raises(self):
        a, b = socket.socketpair()
        try:
            buf = bytearray(wire.frame_msg({"type": "fin"}))
            buf[-1] ^= 0xFF  # flip a payload byte: CRC must catch it
            a.sendall(bytes(buf))
            with pytest.raises(wire.FrameError):
                wire.recv_msg(b)
        finally:
            a.close()
            b.close()

    def test_torn_frame_raises(self):
        a, b = socket.socketpair()
        try:
            buf = wire.frame_msg({"type": "fin", "chunks": 9})
            a.sendall(buf[:len(buf) // 2])
            a.close()
            with pytest.raises(wire.FrameError):
                wire.recv_msg(b)
        finally:
            b.close()

    def test_ops_wire_round_trip(self):
        ops = [make_op(index=0, time=1, type="invoke", process=2,
                       f="write", value=3)]
        back = wire.ops_from_wire(wire.ops_to_wire(ops))
        assert back[0].to_dict() == ops[0].to_dict()


# ---------------------------------------------------------------------------
# the WAL
# ---------------------------------------------------------------------------

class TestWAL:
    def test_append_replay_round_trip(self, tmp_path):
        p = tmp_path / "t.wal"
        w = fwal.RunWAL(p)
        w.append({"t": "hello", "tenant": "a", "run": "r",
                  "model": "cas-register", "weight": 1.0})
        w.append({"t": "chunk", "seq": 1, "ops": [{"f": "read"}]})
        w.append({"t": "chunk", "seq": 2, "ops": [{"f": "write"}]})
        w.append({"t": "fin", "chunks": 2})
        w.close()
        folded = fwal.replay(p)
        assert folded["last_seq"] == 2
        assert folded["fin"]["chunks"] == 2
        assert folded["hello"]["model"] == "cas-register"

    def test_torn_tail_dropped(self, tmp_path):
        p = tmp_path / "t.wal"
        w = fwal.RunWAL(p)
        w.append({"t": "chunk", "seq": 1, "ops": []})
        w.append({"t": "chunk", "seq": 2, "ops": []})
        w.close()
        raw = p.read_bytes()
        p.write_bytes(raw[:-3])  # tear the tail record
        folded = fwal.replay(p)
        assert folded["last_seq"] == 1  # seq 2 must be re-sent

    def test_duplicate_seq_first_wins(self, tmp_path):
        p = tmp_path / "t.wal"
        w = fwal.RunWAL(p)
        w.append({"t": "chunk", "seq": 1, "ops": [{"v": "first"}]})
        w.append({"t": "chunk", "seq": 1, "ops": [{"v": "second"}]})
        w.close()
        assert fwal.replay(p)["chunks"][1] == [{"v": "first"}]

    def test_seq_gap_truncates_resume_point(self, tmp_path):
        p = tmp_path / "t.wal"
        w = fwal.RunWAL(p)
        w.append({"t": "chunk", "seq": 1, "ops": []})
        w.append({"t": "chunk", "seq": 3, "ops": []})
        w.close()
        folded = fwal.replay(p)
        assert folded["last_seq"] == 1
        assert 3 not in folded["chunks"]

    def test_verdict_write_deterministic_and_atomic(self, tmp_path):
        v = {"run": "r", "result": {"valid?": True, "z": 1, "a": 2}}
        fwal.write_verdict(tmp_path, "t", "r", v)
        b1 = fwal.verdict_path(tmp_path, "t", "r").read_bytes()
        fwal.write_verdict(tmp_path, "t", "r", dict(reversed(
            list(v.items()))))
        b2 = fwal.verdict_path(tmp_path, "t", "r").read_bytes()
        assert b1 == b2  # key order can't change the bytes
        assert not list(tmp_path.glob("**/*.tmp"))

    def test_unsafe_names_rejected(self):
        assert not fwal.safe_name("../etc")
        assert not fwal.safe_name(".hidden")
        assert not fwal.safe_name("a/b")
        assert fwal.safe_name("tenant-1.run_2")


# ---------------------------------------------------------------------------
# wgl.check_slices — the fleet's batching entry point
# ---------------------------------------------------------------------------

class TestCheckSlices:
    def test_matches_host_reach(self):
        from jepsen_tpu.tpu import encode as enc_mod

        m = models.cas_register()
        slices = []
        expect = []
        for seed in (1, 2, 3):
            enc = enc_mod.encode(m, seeded_hist(seed, 120))
            cuts = wgl.valid_cut_points(enc)
            hi = int(cuts[len(cuts) // 2]) if len(cuts) else enc.m
            seg = enc.segment(0, hi)
            slices.append((seg, 0))
            expect.append(wgl.search_host_reach(seg))
        out, unk = wgl.check_slices(slices)
        assert not unk.any()
        assert [int(x) for x in out] == expect

    def test_shared_enc_multiple_start_states(self):
        from jepsen_tpu.tpu import encode as enc_mod

        m = models.cas_register()
        enc = enc_mod.encode(m, seeded_hist(4, 80))
        seg = enc.segment(0, min(enc.m, 40))
        rows = [(seg.with_init(s), s)
                for s in range(min(enc.n_states, 3))]
        out, unk = wgl.check_slices(rows)
        assert len(out) == len(rows)
        for (sl, s), mask, u in zip(rows, out, unk):
            if not u:
                assert int(mask) == wgl.search_host_reach(sl)

    def test_empty(self):
        out, unk = wgl.check_slices([])
        assert len(out) == 0 and len(unk) == 0


# ---------------------------------------------------------------------------
# scheduler: weighted fairness, cross-tenant packing, no wedged queues
# ---------------------------------------------------------------------------

class TestScheduler:
    def test_weighted_fair_drain(self):
        s = fsched.Scheduler(max_batch=12)
        s.set_weight("heavy", 2.0)
        s.set_weight("light", 1.0)
        for i in range(20):
            s.submit("slice", "heavy", "r", i)
            s.submit("slice", "light", "r", i)
        with s._lock:
            batch = s._drain_fair_locked()
        by = {}
        for item in batch:
            by[item.tenant] = by.get(item.tenant, 0) + 1
        # a 2:1 weight ratio drains a backlogged round 2:1
        assert by["heavy"] == 2 * by["light"]

    def test_idle_tenant_share_redistributed(self):
        s = fsched.Scheduler(max_batch=8)
        s.set_weight("idle", 10.0)  # huge weight, zero work
        for i in range(8):
            s.submit("slice", "busy", "r", i)
        with s._lock:
            batch = s._drain_fair_locked()
        assert len(batch) == 8  # busy gets the whole batch

    def test_stop_resolves_leftovers_no_wedge(self):
        s = fsched.Scheduler()
        item = s.submit("final", "t", "r",
                        {"engine": "wgl", "model": "cas-register",
                         "history": History([])})
        s.stop()  # never started: queued work must still resolve
        assert item.done.wait(timeout=5)
        assert item.result["valid?"] == "unknown"

    def test_batch_failure_never_wedges(self, monkeypatch):
        s = fsched.Scheduler()
        monkeypatch.setattr(
            wgl, "analysis_batch_streamed",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("boom")))
        items = [s.submit("final", "t", f"r{i}",
                          {"engine": "wgl", "model": "cas-register",
                           "history": seeded_hist(1, 40)})
                 for i in range(2)]
        with s._lock:
            batch = s._drain_fair_locked()
        s._run_batch(batch)
        for i in items:
            assert i.done.is_set()
            assert i.result["valid?"] == "unknown"

    def test_breaker_opens_then_host_floor_still_correct(
            self, monkeypatch):
        # SYSTEMIC failure: every run in the batch dies on device, so
        # per-run attribution finds no survivor and the FLEET breaker
        # (not per-run quarantine) takes the hit
        s = fsched.Scheduler()
        s._breaker.cooldown_s = 3600  # stay open for the test
        monkeypatch.setattr(
            wgl, "analysis_batch_streamed",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("device dead")))
        hist = seeded_hist(2, 60)
        for i in range(fsched.BREAKER_THRESHOLD):
            items = [s.submit("final", "t", f"r{i}{j}",
                              {"engine": "wgl",
                               "model": "cas-register",
                               "history": hist})
                     for j in range(2)]
            with s._lock:
                batch = s._drain_fair_locked()
            s._run_batch(batch)
            for it in items:
                assert it.result["valid?"] == "unknown"
        assert s._breaker.opened_at is not None
        # systemic failure opens the breaker WITHOUT quarantining
        # anyone — no single run was at fault
        assert s.stats()["quarantine"] == []
        # breaker open: finals route to the pure-host search and the
        # verdict is still CORRECT (slower, never wrong)
        item = s.submit("final", "t", "rz",
                        {"engine": "wgl", "model": "cas-register",
                         "history": hist})
        with s._lock:
            batch = s._drain_fair_locked()
        s._run_batch(batch)
        assert item.result["valid?"] is True
        assert s.stats()["host_floor"] == 1

    def test_poison_run_quarantined_not_systemic(self, monkeypatch):
        # ONE run's history kills the shared launch: attribution
        # bisects along run boundaries, quarantines the offender to
        # the solo host lane, and the fleet breaker stays CLOSED —
        # healthy runs keep their device-batched verdicts
        s = fsched.Scheduler()
        poison = seeded_hist(3, 60)
        real = wgl.analysis_batch_streamed

        def selective(model, hists, **kw):
            if any(h is poison for h in hists):
                raise RuntimeError("device dead")
            return real(model, hists, **kw)

        monkeypatch.setattr(wgl, "analysis_batch_streamed", selective)
        items = [s.submit("final", "t", f"r{j}",
                          {"engine": "wgl", "model": "cas-register",
                           "history": seeded_hist(10 + j, 60)})
                 for j in range(2)]
        bad = s.submit("final", "t", "rbad",
                       {"engine": "wgl", "model": "cas-register",
                        "history": poison})
        with s._lock:
            batch = s._drain_fair_locked()
        s._run_batch(batch)
        # healthy runs got their verdicts via solo-device retry
        for it in items:
            assert it.result["valid?"] is True
        # the poison run still got a CORRECT verdict (host lane)
        assert bad.result["valid?"] is True
        st = s.stats()
        assert [q["run"] for q in st["quarantine"]] == ["rbad"]
        assert s._breaker.opened_at is None
        # quarantined: the next final for that run skips the shared
        # batch entirely and is served from the host lane
        bad2 = s.submit("final", "t", "rbad",
                        {"engine": "wgl", "model": "cas-register",
                         "history": poison})
        with s._lock:
            batch = s._drain_fair_locked()
        s._run_batch(batch)
        assert bad2.result["valid?"] is True
        assert s._breaker.opened_at is None


# ---------------------------------------------------------------------------
# streaming checks
# ---------------------------------------------------------------------------

class TestStreaming:
    def _drive(self, hist, seed_chunks=100):
        sched = fsched.Scheduler(window_s=0.01).start()
        try:
            sr = fsched.StreamingRun("cas-register", sched, "t", "r")
            ops = list(hist)
            for i in range(0, len(ops), seed_chunks):
                sr.add_ops(ops[i:i + seed_chunks])
            sr.step()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                st = sr.status()
                if st["state"] != "streaming" or \
                        st["checked-frac"] > 0:
                    # one more step to push past the last cut
                    with sr._lock:
                        busy = sr._inflight
                    if not busy:
                        return sr
                time.sleep(0.05)
            return sr
        finally:
            sched.stop()

    def test_valid_stream_tightens(self):
        sr = self._drive(seeded_hist(21, 600))
        st = sr.status()
        assert st["state"] in ("streaming",)
        assert st["checked-frac"] > 0  # the prefix is certified

    def test_corrupt_stream_goes_tentative_invalid(self):
        telemetry.reset()
        h, _ = synth.corrupt_register_history(
            synth.register_history(600, seed=22), at_frac=0.2)
        sched = fsched.Scheduler(window_s=0.01).start()
        try:
            sr = fsched.StreamingRun("cas-register", sched, "t", "r")
            ops = list(h)
            deadline = time.monotonic() + 60
            i = 0
            while i < len(ops) and time.monotonic() < deadline:
                sr.add_ops(ops[i:i + 100])
                i += 100
                sr.step()
                if sr.status()["state"] == "tentative-invalid":
                    break
            deadline = time.monotonic() + 30
            while sr.status()["state"] == "streaming" and \
                    time.monotonic() < deadline:
                sr.step()
                time.sleep(0.05)
            # the verdict tightened to invalid BEFORE fin
            assert sr.status()["state"] == "tentative-invalid"
        finally:
            sched.stop()

    def test_unsupported_model_degrades_honestly(self):
        sched = fsched.Scheduler()
        sr = fsched.StreamingRun("no-such-model", sched, "t", "r")
        sr.add_ops(list(seeded_hist(1, 200)))
        assert sr.status()["state"] == "unsupported"


# ---------------------------------------------------------------------------
# server end-to-end
# ---------------------------------------------------------------------------

class TestServerE2E:
    def test_single_tenant_verdict_matches_solo(self, tmp_path):
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            for name, corrupt in (("valid", False), ("bad", True)):
                h = seeded_hist(SEED, 300, corrupt=corrupt)
                env = stream_run(srv.addr, "t1", f"r-{name}", h)
                assert_verdict_matches_solo(h, env["result"],
                                            solo_verdict(h))
        finally:
            srv.stop()

    def test_model_initial_value_in_spec(self, tmp_path):
        """A DB that seeds its register (AtomDB writes 0) checked
        against an initial-None model is PROVABLY nonlinearizable on
        the first read — so the wire's model spec must carry the
        initial value (the verify-skill gotcha, fleet edition)."""
        from jepsen_tpu.history import op as mk

        ops = []
        for i, (t, p, f, v) in enumerate((
                ("invoke", 0, "read", None), ("ok", 0, "read", 0),
                ("invoke", 1, "write", 3), ("ok", 1, "write", 3),
                ("invoke", 0, "read", None), ("ok", 0, "read", 3))):
            ops.append(mk(index=i, time=i, type=t, process=p, f=f,
                          value=v))
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            c0 = fclient.FleetClient(srv.addr, "t", "no-initial",
                                     model="register")
            c0.send_chunk(ops)
            r0 = c0.finish()["result"]
            assert r0["valid?"] is False  # read 0 vs initial None
            c1 = fclient.FleetClient(srv.addr, "t", "seeded",
                                     model="register", initial=0)
            c1.send_chunk(ops)
            r1 = c1.finish()["result"]
            assert r1["valid?"] is True
            certify.validate(History(ops), r1["certificate"])
        finally:
            srv.stop()

    def test_stats_and_prometheus_labels(self, tmp_path):
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            stream_run(srv.addr, "acme", "r1", seeded_hist(1, 120))
            st = srv.stats()
            assert st["tenants"]["acme"]["verdicts"] == 1
            assert st["tenants"]["acme"]["ops"] == len(
                seeded_hist(1, 120))
            text = srv.prometheus_text()
            assert 'jepsen_fleet_tenant_ops{tenant="acme"}' in text
            assert "jepsen_fleet_scheduler_launches" in text
        finally:
            srv.stop()

    def test_duplicate_and_out_of_order_chunks(self, tmp_path):
        """Raw-socket protocol check: duplicates re-ack idempotently,
        gaps resync — no corruption either way."""
        srv = fserver.FleetServer(tmp_path / "fleet",
                                  stream_checks=False).start()
        try:
            s = socket.create_connection(srv.addr, timeout=5)
            wire.send_magic(s)
            wire.send_msg(s, {"type": "hello", "tenant": "t",
                              "run": "r", "model": "cas-register"})
            assert wire.recv_msg(s)["type"] == "helloed"
            ops = wire.ops_to_wire(list(seeded_hist(2, 30)))
            wire.send_msg(s, {"type": "chunk", "seq": 1, "ops": ops})
            assert wire.recv_msg(s)["seq"] == 1
            # duplicate: idempotent re-ack
            wire.send_msg(s, {"type": "chunk", "seq": 1, "ops": ops})
            assert wire.recv_msg(s)["seq"] == 1
            # gap: resync ack names the journaled prefix
            wire.send_msg(s, {"type": "chunk", "seq": 5, "ops": ops})
            r = wire.recv_msg(s)
            assert r["seq"] == 1 and r.get("resync")
            s.close()
            folded = fwal.replay(
                fwal.wal_path(tmp_path / "fleet", "t", "r"))
            assert folded["last_seq"] == 1  # journaled exactly once
        finally:
            srv.stop()

    def test_fleet_page_and_metrics(self, tmp_path):
        from jepsen_tpu import web

        base = tmp_path / "store"
        # no server: the page renders an honest absence
        assert "no fleet server" in web.fleet_html(base)
        srv = fserver.FleetServer(base / "fleet").start()
        try:
            stream_run(srv.addr, "acme", "r1", seeded_hist(1, 100))
            html = web.fleet_html(base)
            assert "acme" in html and "verdicts" in html
            st, addr = web._fleet_stats(base)
            assert st is not None
            text = fserver.prometheus_from_stats(st)
            assert 'tenant="acme"' in text
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_ninth_tenant_rejected_in_flight_unharmed(self, tmp_path):
        quotas = fserver.Quotas(max_tenants=3, max_total_streams=8)
        srv = fserver.FleetServer(tmp_path / "fleet",
                                  quotas=quotas).start()
        try:
            hists = {f"t{i}": seeded_hist(100 + i, 200)
                     for i in range(3)}
            clients = {}
            for t, h in hists.items():
                c = fclient.FleetClient(srv.addr, t, "r",
                                        io_timeout_s=3)
                c.send_chunk(list(h)[:50])  # streams now in flight
                clients[t] = c
            # the over-quota tenant is REJECTED with retry-after...
            with pytest.raises(fclient.FleetRejected) as ei:
                fclient.FleetClient(srv.addr, "t-late", "r",
                                    io_timeout_s=3).send_chunk(
                    list(hists["t0"])[:10])
            assert ei.value.retry_after is not None
            assert srv.stats()["rejected"] >= 1
            # ...and every in-flight stream completes unharmed
            for t, c in clients.items():
                ops = list(hists[t])
                for i in range(50, len(ops), 50):
                    c.send_chunk(ops[i:i + 50])
                env = c.finish()
                assert_verdict_matches_solo(hists[t], env["result"],
                                            solo_verdict(hists[t]))
        finally:
            srv.stop()

    def test_colliding_run_name_rejected_not_stale_verdict(
            self, tmp_path):
        """Re-submitting a DIFFERENT history under an existing run
        name must fail loudly — never silently return the old run's
        verdict as if computed on the new data. claim() stays the
        legitimate way to fetch an existing verdict."""
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            h1 = seeded_hist(61, 150)
            env1 = stream_run(srv.addr, "t", "r", h1)
            assert env1["result"]["valid?"] is True
            c2 = fclient.FleetClient(srv.addr, "t", "r",
                                     io_timeout_s=3)
            with pytest.raises(fclient.FleetError,
                               match="colliding run name"):
                c2.send_chunk(list(seeded_hist(62, 150))[:50])
            # the fresh-client verdict fetch still works
            env = fclient.FleetClient(srv.addr, "t", "r",
                                      io_timeout_s=3).claim()
            assert env["result"]["valid?"] is True
        finally:
            srv.stop()

    def test_bad_names_and_models_rejected_without_retry(
            self, tmp_path):
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            with pytest.raises(fclient.FleetRejected) as ei:
                fclient.FleetClient(srv.addr, "../evil", "r").status()
            assert ei.value.retry_after is None
            with pytest.raises(fclient.FleetRejected):
                fclient.FleetClient(srv.addr, "t", "r",
                                    model="no-such-model").status()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# crash schedules
# ---------------------------------------------------------------------------

class TestCrashSafety:
    def test_sigkill_midstream_replays_byte_identical(self, tmp_path):
        h = seeded_hist(SEED, 400)
        ops = list(h)
        chunks = [ops[i:i + 50] for i in range(0, len(ops), 50)]

        # clean reference
        ref_base = tmp_path / "ref"
        srv = fserver.FleetServer(ref_base).start()
        c = fclient.FleetClient(srv.addr, "t1", "r1", io_timeout_s=3)
        for ch in chunks:
            c.send_chunk(ch)
        c.finish()
        srv.stop()
        ref = fwal.verdict_path(ref_base, "t1", "r1").read_bytes()

        # SIGKILL mid-stream, restart on the same WAL dir
        base = tmp_path / "crash"
        srv = fserver.FleetServer(base).start()
        c = fclient.FleetClient(srv.addr, "t1", "r1", io_timeout_s=2)
        for ch in chunks[:4]:
            c.send_chunk(ch)
        port = srv.addr[1]
        srv.kill()
        srv2 = fserver.FleetServer(base, port=port).start()
        for ch in chunks[4:]:
            c.send_chunk(ch)
        env = c.finish()
        assert env["result"]["valid?"] is True
        got = fwal.verdict_path(base, "t1", "r1").read_bytes()
        assert got == ref  # byte-identical replay
        srv2.stop()

    def test_fin_crash_recovery_resubmits(self, tmp_path):
        h = seeded_hist(SEED, 400)
        ops = list(h)
        base = tmp_path / "fleet"
        sched = fsched.Scheduler()
        srv = fserver.FleetServer(base, scheduler=sched).start()
        c = fclient.FleetClient(srv.addr, "t1", "r1", io_timeout_s=1)
        for i in range(0, len(ops), 50):
            c.send_chunk(ops[i:i + 50])
        sched._stop.set()  # freeze: the fin's final check never runs
        time.sleep(0.4)
        with pytest.raises(fclient.FleetError):
            c.finish(timeout_s=2)
        srv.kill()
        # restart: recovery finds fin-without-verdict and re-submits
        srv2 = fserver.FleetServer(base).start()
        assert srv2.stats()["recovered"] == 1
        env = fclient.FleetClient(srv2.addr, "t1", "r1",
                                  io_timeout_s=3).claim()
        assert_verdict_matches_solo(h, env["result"], solo_verdict(h))
        srv2.stop()


# ---------------------------------------------------------------------------
# the acceptance invariants: concurrency, chaos, kill — vs solo
# ---------------------------------------------------------------------------

def _concurrent_runs(addr, hists, transports=None, barrier=None,
                     out=None, chunk=50):
    out = out if out is not None else {}
    errs = []

    def one(tenant, h):
        try:
            t = (transports or {}).get(tenant)
            c = fclient.FleetClient(addr, tenant, "r", model="cas-register",
                                    transport=t, io_timeout_s=2.0)
            ops = list(h)
            deadline = time.monotonic() + 180
            i = 0
            while i < len(ops):
                try:
                    c.send_chunk(ops[i:i + chunk])
                    i += chunk
                except fclient.FleetError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.2)
            if barrier is not None:
                barrier.wait(timeout=60)
            out[tenant] = c.finish(timeout_s=180)
            c.close()
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append((tenant, e))

    threads = [threading.Thread(target=one, args=(t, h), daemon=True)
               for t, h in hists.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errs, errs
    return out


class TestMultiTenantE2E:
    def test_eight_tenants_identical_to_solo_and_batched(
            self, tmp_path):
        telemetry.reset()
        # 7 valid + 1 seeded-anomaly run; fins synchronized so the
        # finals land in shared launches
        hists = {f"t{i}": seeded_hist(500 + i, 240, corrupt=(i == 3))
                 for i in range(8)}
        sched = fsched.Scheduler(window_s=0.4)
        srv = fserver.FleetServer(tmp_path / "fleet",
                                  scheduler=sched).start()
        try:
            barrier = threading.Barrier(8)
            out = _concurrent_runs(srv.addr, hists, barrier=barrier)
            assert set(out) == set(hists)
            for t, h in hists.items():
                assert_verdict_matches_solo(h, out[t]["result"],
                                            solo_verdict(h))
            stats = srv.stats()
            st = stats["scheduler"]
            # continuous batching actually happened ACROSS tenants
            assert st["cross_tenant_launches"] >= 1
            assert st["max_tenants_in_launch"] >= 2
            assert st["final_hists"] == 8
            # launch classes split: the blended hists_per_launch bug
            assert st["slice_launches"] + st["final_launches"] == \
                st["launches"]
            # the flight recorder's acceptance invariants (ISSUE 17):
            # a schema-valid latency block on EVERY verdict...
            for t in hists:
                lat = out[t].get("latency")
                flightrec.validate_latency(lat)
                assert lat["total_ms"] > 0
            # ...a decision log whose reason counts sum to the total
            # launches, per-class occupancy in range...
            fr = stats["flightrec"]
            assert fr["enabled"] is True
            assert sum(fr["decisions"].values()) == fr["launches"] \
                == st["launches"]
            assert fr["verdict_ms"]["n"] == 8
            assert set(fr["tenants"]) == set(hists)
            for cls in ("slice", "final"):
                assert 0.0 <= fr["classes"][cls]["occupancy"] <= 1.0
            # ...schema-valid records and a validating Perfetto
            # fleet-session export with per-tenant + device tracks
            recs = srv.flightrec.records()
            flightrec.validate_records(recs)
            doc = rtrace.fleet_chrome_trace(recs)
            assert rtrace.validate_chrome_trace(doc) > 0
            tracks = {e["args"]["name"]
                      for e in doc["traceEvents"]
                      if e["ph"] == "M"
                      and e["name"] == "thread_name"}
            assert set(hists) <= tracks
            assert "device launches" in tracks
            # ...and scrape-parseable tenant-labeled /metrics samples
            prom = fserver.prometheus_from_stats(stats)
            assert flightrec.validate_prometheus(prom) > 0
            assert 'tenant="t3"' in prom
        finally:
            srv.stop()


class TestChaosFleet:
    def test_chaos_transport_runs_identical_to_solo(self, tmp_path):
        """Satellite 1's tier-1 invariant: N concurrent seeded runs
        through ONE chaos-wrapped server — frames dropped, duplicated,
        reordered, torn — still yield verdicts + certificates
        identical to solo runs."""
        hists = {f"t{i}": seeded_hist(700 + i, 200,
                                      corrupt=(i == 1))
                 for i in range(4)}
        transports = {t: chaos.ChaosFleetTransport(seed=SEED + i)
                      for i, t in enumerate(hists)}
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            out = _concurrent_runs(srv.addr, hists,
                                   transports=transports, chunk=40)
            for t, h in hists.items():
                assert_verdict_matches_solo(h, out[t]["result"],
                                            solo_verdict(h))
            # the schedule actually injected faults
            total = sum(sum(tr.tally.values())
                        for tr in transports.values())
            assert total > 0, "chaos rates injected nothing"
        finally:
            srv.stop()

    def test_chaos_plus_midstream_kill(self, tmp_path):
        """The full acceptance schedule: chaos framing AND a
        mid-stream SIGKILL + restart, concurrently."""
        hists = {f"t{i}": seeded_hist(800 + i, 200)
                 for i in range(3)}
        transports = {t: chaos.ChaosFleetTransport(seed=9000 + i)
                      for i, t in enumerate(hists)}
        base = tmp_path / "fleet"
        srv_box = [fserver.FleetServer(base).start()]
        port = srv_box[0].addr[1]

        def killer():
            time.sleep(1.0)
            srv_box[0].kill()
            srv_box[0] = fserver.FleetServer(base, port=port).start()

        kt = threading.Thread(target=killer, daemon=True)
        kt.start()
        try:
            out = _concurrent_runs(srv_box[0].addr, hists,
                                   transports=transports, chunk=30)
            kt.join(timeout=30)
            for t, h in hists.items():
                assert_verdict_matches_solo(h, out[t]["result"],
                                            solo_verdict(h))
        finally:
            srv_box[0].stop()


# ---------------------------------------------------------------------------
# the flight recorder in the fleet (ISSUE 17)
# ---------------------------------------------------------------------------

class TestFlightRecorderFleet:
    def test_verdict_files_byte_identical_with_recorder_off(
            self, tmp_path):
        """The latency block rides NEXT to the verdict, never inside
        it: the verdict file's bytes must not change with the
        recorder on vs disabled."""
        h = seeded_hist(SEED, 200)
        envs = {}
        for name, on in (("on", True), ("off", False)):
            base = tmp_path / name
            srv = fserver.FleetServer(base, flightrec=on).start()
            try:
                envs[name] = stream_run(srv.addr, "t", "r", h)
            finally:
                srv.stop()
        on_b = fwal.verdict_path(tmp_path / "on", "t", "r").read_bytes()
        off_b = fwal.verdict_path(tmp_path / "off", "t",
                                  "r").read_bytes()
        assert on_b == off_b
        # the wire envelope differs exactly by the latency sibling
        flightrec.validate_latency(envs["on"]["latency"])
        assert "latency" not in envs["off"]
        assert envs["on"]["result"] == envs["off"]["result"]

    def test_chaos_frames_never_orphan_or_double_count_spans(
            self, tmp_path):
        """Chaos parity: dropped/duplicated/reordered frames may
        retransmit forever, but every journaled (tenant, run, seq)
        records EXACTLY one chunk span — no orphans for dropped
        frames, no double counts for duplicated ones."""
        hists = {f"t{i}": seeded_hist(1300 + i, 150)
                 for i in range(3)}
        transports = {t: chaos.ChaosFleetTransport(seed=SEED + 7 * i)
                      for i, t in enumerate(hists)}
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            out = _concurrent_runs(srv.addr, hists,
                                   transports=transports, chunk=30)
            assert sum(sum(tr.tally.values())
                       for tr in transports.values()) > 0
            recs = srv.flightrec.records()
            # validate_records raises on duplicate (tenant, run, seq)
            flightrec.validate_records(recs)
            chunk_spans = {(r["tenant"], r["seq"]) for r in recs
                           if r["kind"] == "chunk"}
            # Per tenant: seqs form a gapless 1..max run. A dropped
            # frame that orphaned a span would leave a gap; a
            # duplicated frame that double-counted would have tripped
            # validate_records above. (The exact count is schedule-
            # dependent — a chaos-failed send can resume the staged
            # chunk or stage a fresh seq — so contiguity, not count,
            # is the invariant.)
            for t in hists:
                seqs = {s for (tt, s) in chunk_spans if tt == t}
                assert seqs, f"{t}: no chunk spans journaled"
                assert seqs == set(range(1, max(seqs) + 1)), (
                    f"{t}: gap in journaled seqs {sorted(seqs)}")
            for t in hists:
                flightrec.validate_latency(out[t]["latency"])
        finally:
            srv.stop()

    def test_sigkill_replayed_verdicts_carry_replay_blocks(
            self, tmp_path):
        """A SIGKILL'd server's replayed verdicts still carry a
        complete latency block — replay-annotated, with the
        ingest-side slices honestly zero (they died with the old
        process)."""
        h = seeded_hist(SEED, 300)
        ops = list(h)
        base = tmp_path / "fleet"
        sched = fsched.Scheduler()
        srv = fserver.FleetServer(base, scheduler=sched).start()
        c = fclient.FleetClient(srv.addr, "t1", "r1", io_timeout_s=1)
        for i in range(0, len(ops), 50):
            c.send_chunk(ops[i:i + 50])
        sched._stop.set()  # freeze: the fin's final check never runs
        time.sleep(0.4)
        with pytest.raises(fclient.FleetError):
            c.finish(timeout_s=2)
        srv.kill()
        # restart: recovery re-submits the fin-without-verdict run
        srv2 = fserver.FleetServer(base).start()
        try:
            env = fclient.FleetClient(srv2.addr, "t1", "r1",
                                      io_timeout_s=3).claim()
            lat = env["latency"]
            flightrec.validate_latency(lat)
            assert lat["replay"] is True
            assert lat["ingest_wait"] == 0.0
            assert lat["wal_fsync"] == 0.0

            # the verdict-file-served path (no recompute) also
            # carries a complete replay block after ANOTHER restart
            srv2.stop()
            srv3 = fserver.FleetServer(base).start()
            env = fclient.FleetClient(srv3.addr, "t1", "r1",
                                      io_timeout_s=3).claim()
            flightrec.validate_latency(env["latency"])
            assert env["latency"]["replay"] is True
            srv3.stop()
        finally:
            pass

    def test_graceful_stop_drains_with_drain_reason(self, tmp_path):
        """stop() flushes queued work as `drain` launches; every
        launch still lands in the decision log."""
        sched = fsched.Scheduler(window_s=30.0)  # never times out
        srv = fserver.FleetServer(tmp_path / "fleet",
                                  scheduler=sched).start()
        h = seeded_hist(SEED, 120)
        c = fclient.FleetClient(srv.addr, "t", "r", io_timeout_s=3)
        for i in range(0, 120, 40):
            c.send_chunk(list(h)[i:i + 40])

        def fin():
            try:
                c.finish(timeout_s=30)
            except fclient.FleetError:
                pass

        ft = threading.Thread(target=fin, daemon=True)
        ft.start()
        time.sleep(0.5)  # the final sits in the 30s batching window
        srv.stop()
        ft.join(timeout=10)
        snap = srv.flightrec.snapshot()
        assert snap["decisions"]["drain"] >= 1
        assert sum(snap["decisions"].values()) == snap["launches"]

    def test_snapshot_survives_sigkill_and_folds(self, tmp_path):
        """flightrec.json persists per verdict; a restarted server
        folds its predecessor's SLO history back in."""
        base = tmp_path / "fleet"
        srv = fserver.FleetServer(base).start()
        stream_run(srv.addr, "t", "r1", seeded_hist(SEED, 150))
        before = srv.flightrec.snapshot()["verdict_ms"]["n"]
        assert before >= 1
        srv.kill()
        srv2 = fserver.FleetServer(base).start()
        try:
            s = srv2.flightrec.snapshot()
            assert s["verdict_ms"]["n"] == before
            assert s["tenants"]["t"]["verdict_ms"]["n"] == before
        finally:
            srv2.stop()

    def test_client_ack_histogram_rides_result_summary(self):
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            srv = fserver.FleetServer(td).start()
            try:
                c = fclient.FleetClient(srv.addr, "t", "r",
                                        io_timeout_s=3)
                ops = list(seeded_hist(SEED, 100))
                for i in range(0, 100, 25):
                    c.send_chunk(ops[i:i + 25])
                assert c.ack_ms.n == 4
                streamer = fclient.FleetStreamer(None, c)
                out = streamer.result_summary(timeout_s=60)
                assert out["ack_ms"]["n"] == 4
                assert out["ack_ms"]["p99"] >= out["ack_ms"]["p50"] \
                    >= 0
                flightrec.validate_latency(out["verdict"]["latency"])
            finally:
                srv.stop()

    def test_quarantine_events_in_recorder_and_metrics(
            self, tmp_path, monkeypatch):
        """Poison-run quarantine is observable end to end: the flight
        recorder journals a schema-valid quarantine record, /metrics
        exports quarantined_runs plus per-action event counters, and
        the host-lane launch lands in the decision log under its own
        "quarantine" reason — while a healthy neighbor keeps its
        device-batched verdict."""
        MARK = 888888  # wire round-trips rebuild ops, so a sentinel
        poison = []    # value tags the poison history, not identity
        for f, v in [("write", MARK), ("read", MARK)] * 10:
            poison.append(make_op(
                index=len(poison), time=len(poison), type="invoke",
                process=0, f=f, value=v if f == "write" else None))
            poison.append(make_op(
                index=len(poison), time=len(poison), type="ok",
                process=0, f=f, value=v))
        real = wgl.analysis_batch_streamed

        def selective(model, hists, **kw):
            if any(any(o.f == "write" and o.value == MARK for o in h)
                   for h in hists):
                raise RuntimeError("injected poison launch death")
            return real(model, hists, **kw)

        monkeypatch.setattr(wgl, "analysis_batch_streamed", selective)
        h = seeded_hist(61, 200)
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            cp = fclient.FleetClient(srv.addr, "tbad", "rbad",
                                     model="cas-register")
            cp.send_chunk(poison)
            envp = cp.finish(timeout_s=120)
            cp.close()
            env = stream_run(srv.addr, "tgood", "r", h)
            # host lane: slower, never wrong — and never starved
            assert envp["result"]["valid?"] is True
            assert_verdict_matches_solo(h, env["result"],
                                        solo_verdict(h))
            recs = srv.flightrec.records()
            flightrec.validate_records(recs)
            q = [r for r in recs if r["kind"] == "quarantine"]
            assert [(r["tenant"], r["run"], r["action"])
                    for r in q] == [("tbad", "rbad", "quarantined")]
            stats = srv.stats()
            assert [x["run"] for x
                    in stats["scheduler"]["quarantine"]] == ["rbad"]
            prom = fserver.prometheus_from_stats(stats)
            assert flightrec.validate_prometheus(prom) > 0
            assert "jepsen_fleet_quarantined_runs 1" in prom
            assert ('jepsen_fleet_quarantine_events_total'
                    '{action="quarantined"} 1') in prom
            assert "jepsen_fleet_wal_sheds 0" in prom
            fr = stats["flightrec"]
            assert fr["quarantine"].get("quarantined") == 1
            assert fr["decisions"].get("quarantine", 0) >= 1
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# interpreter hook (core.run integration)
# ---------------------------------------------------------------------------

class TestInterpreterHook:
    def _test_map(self, tmp_path, name, addr):
        state = testing.AtomState()
        test = testing.noop_test()
        import random as _random

        rng = _random.Random(5)

        def one():
            if rng.random() < 0.5:
                return {"f": "read"}
            return {"f": "write", "value": rng.randrange(5)}

        test.update(
            name=name, store_base=str(tmp_path / "store"),
            nodes=["n1", "n2"], concurrency=2,
            client=testing.AtomClient(state, latency_s=0.0002),
            generator=gen.clients(gen.limit(120, one)),
            fleet={"addr": addr, "tenant": "hook",
                   "model": "cas-register", "chunk_ops": 32})
        return test

    def test_live_run_streams_and_attaches_verdict(self, tmp_path):
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            host, port = srv.addr
            t = core.run(self._test_map(tmp_path, "fleet-hook",
                                        f"{host}:{port}"))
            fl = t["results"]["fleet"]
            assert "verdict" in fl, fl
            assert fl["verdict"]["result"]["valid?"] is True
            certify.validate(t["history"],
                             fl["verdict"]["result"]["certificate"])
            assert srv.stats()["tenants"]["hook"]["ops"] == len(
                t["history"])
        finally:
            srv.stop()

    def test_unreachable_fleet_falls_back_honestly(self, tmp_path):
        # a port nothing listens on: the run must complete locally
        # with an honest unavailable marker
        t = core.run(self._test_map(tmp_path, "fleet-fallback",
                                    "127.0.0.1:9"))
        assert t["results"]["valid?"] is not None
        fl = t["results"]["fleet"]
        assert "unavailable" in fl


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCli:
    def test_submit_and_status(self, tmp_path, capsys):
        from jepsen_tpu import cli as jcli
        from jepsen_tpu.store import format as sformat

        h = seeded_hist(31, 150)
        run_dir = tmp_path / "some-run"
        sformat.write_history(run_dir / "history.jlog", list(h))
        srv = fserver.FleetServer(tmp_path / "fleet").start()
        try:
            host, port = srv.addr
            spec = jcli.fleet_cmd()["fleet"]
            import argparse

            p = spec["parser_fn"](argparse.ArgumentParser())
            opts = p.parse_args(
                ["submit", str(run_dir), "--addr", f"{host}:{port}",
                 "--tenant", "cli-t", "--chunk-ops", "40"])
            assert spec["run"](opts) == 0
            out = json.loads(capsys.readouterr().out)
            assert out["result"]["valid?"] is True
            opts = p.parse_args(
                ["status", "--addr", f"{host}:{port}"])
            assert spec["run"](opts) == 0
            st = json.loads(capsys.readouterr().out)
            assert st["tenants"]["cli-t"]["verdicts"] == 1
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# satellite 2: shared-ledger concurrent-append safety
# ---------------------------------------------------------------------------

class TestSharedLedgerAppends:
    def test_two_writer_ledger_stress(self, tmp_path):
        path = tmp_path / "shared.jsonl"
        n_per = 200
        errs = []

        def writer(wid):
            try:
                for i in range(n_per):
                    util.atomic_append_line(
                        path, json.dumps({"w": wid, "i": i,
                                          "pad": "x" * 200}))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=writer, args=(w,))
              for w in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        # every line parses whole — lines interleave, bytes never do
        lines = path.read_text().splitlines()
        assert len(lines) == 2 * n_per
        seen = {(0, -1), (1, -1)}
        for ln in lines:
            d = json.loads(ln)  # no spliced lines
            assert len(d["pad"]) == 200
        by = {}
        for ln in lines:
            d = json.loads(ln)
            by.setdefault(d["w"], []).append(d["i"])
        for w, idxs in by.items():
            assert idxs == sorted(idxs)  # per-writer order preserved

    def test_two_writer_atlas_stress(self, tmp_path):
        from jepsen_tpu import coverage

        base = tmp_path
        errs = []

        def writer(wid):
            try:
                for i in range(60):
                    entry = {"run": f"r{wid}-{i}", "ts": 1.0,
                             "workload": "register",
                             "digest": f"d{wid}-{i}",
                             "faults": {}, "anomalies": {}}
                    coverage._append_if_new(
                        base / coverage.ATLAS_FILE, {}, entry)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=writer, args=(w,))
              for w in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        entries = coverage.read_atlas(base / coverage.ATLAS_FILE)
        assert len(entries) == 120  # nothing lost, nothing spliced
        assert len(coverage.dedup_entries(entries)) == 120

    def test_ledger_append_entry_single_write(self, tmp_path,
                                              monkeypatch):
        """One appended line is one os.write: the property that keeps
        concurrent writers from splicing bytes."""
        p = tmp_path / "l.jsonl"
        real, calls = os.write, []

        def write(fd, buf):
            if os.path.samestat(os.fstat(fd), os.stat(p)):
                calls.append(bytes(buf))
            return real(fd, buf)

        monkeypatch.setattr(os, "write", write)
        entry = {"round": 1, "headline": {"value": 1.0}}
        util.atomic_append_line(p, json.dumps(entry))
        util.atomic_append_line(p, json.dumps(entry))
        assert calls == [(json.dumps(entry) + "\n").encode()] * 2
        assert [json.loads(ln) for ln in p.read_text().splitlines()] \
            == [entry, entry]


class TestWriteAll:
    """util.write_all: the short-write loop behind the WAL, checkpoint
    records and the shared-file appends."""

    def test_short_writes_continue_to_the_end(self, tmp_path,
                                              monkeypatch):
        real, sizes = os.write, []
        path = tmp_path / "f"
        fd = os.open(path, os.O_CREAT | os.O_WRONLY)

        def one_byte(wfd, buf):
            if wfd != fd:
                return real(wfd, buf)
            sizes.append(len(buf))
            return real(wfd, bytes(buf[:1]))

        monkeypatch.setattr(os, "write", one_byte)
        try:
            util.write_all(fd, b"abcdef")
        finally:
            monkeypatch.undo()
            os.close(fd)
        assert path.read_bytes() == b"abcdef"
        # each call is handed exactly what is still unwritten
        assert sizes == [6, 5, 4, 3, 2, 1]

    def test_zero_progress_write_raises(self, tmp_path, monkeypatch):
        path = tmp_path / "f"
        fd = os.open(path, os.O_CREAT | os.O_WRONLY)
        real, writes = os.write, iter([2, 0])
        monkeypatch.setattr(
            os, "write",
            lambda wfd, buf: next(writes) if wfd == fd else real(wfd, buf))
        try:
            with pytest.raises(OSError, match="short write"):
                util.write_all(fd, b"abcdef")
        finally:
            monkeypatch.undo()
            os.close(fd)


# ---------------------------------------------------------------------------
# satellite 3: lint coverage of the fleet
# ---------------------------------------------------------------------------

class TestFleetLint:
    def test_fleet_modules_concurrency_clean(self):
        from jepsen_tpu import chaos as chaos_mod
        from jepsen_tpu.analysis import concurrency
        from jepsen_tpu.fleet import client as c
        from jepsen_tpu.fleet import scheduler as s
        from jepsen_tpu.fleet import server as srv
        from jepsen_tpu.tpu import ckpt as ckpt_mod
        from jepsen_tpu.tpu import elle as elle_mod

        fs = []
        for mod in (s, srv, c, chaos_mod, flightrec, ckpt_mod,
                    elle_mod):
            fs.extend(concurrency.scan_module(mod))
        assert [(f.rule, f.kernel, f.site) for f in fs] == []

    def test_fleet_modules_in_driver_list(self):
        from jepsen_tpu.analysis import driver

        names = driver.CONCURRENCY_MODULE_NAMES
        assert "jepsen_tpu.fleet.scheduler" in names
        assert "jepsen_tpu.fleet.server" in names
        assert "jepsen_tpu.fleet.flightrec" in names
        assert "jepsen_tpu.tpu.ckpt" in names
        assert "jepsen_tpu.tpu.elle" in names

    def test_wgl_slices_registered_and_traces(self):
        from jepsen_tpu.analysis import registry

        entry = {e.name: e for e in registry.entries()}["wgl-slices"]
        tr = entry.trace(entry.buckets[0])
        assert tr.name == "wgl-slices"
        assert tr.jaxpr is not None
        # R3's donation source: the packed segment tensors stay
        # donated through the fleet entry point's shared jit factory
        donated = {a.name for a in tr.args if a.donated}
        assert "inv_t" in donated
