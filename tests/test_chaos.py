"""Chaos-rig suite: the harness survives faults injected into ITSELF.

Fixed-seed smoke for tier-1 (ISSUE 5): every test asserts the four
run-level invariants — the run terminates, the history stays
well-formed, teardown heals, the store validates — plus the analysis
invariant: the verdict is True/False/'unknown', never an exception.
"""

import json
import os

import pytest

from jepsen_tpu import chaos, checker, control, core, store, telemetry, testing
from jepsen_tpu import client as jclient
from jepsen_tpu import generator as gen
from jepsen_tpu import nemesis as jnemesis
from jepsen_tpu import net as jnet
from jepsen_tpu.control import retry as retry_mod
from jepsen_tpu.control.core import (Action, Remote, Result,
                                     TransportError)
from jepsen_tpu.control.dummy import DummyRemote, DummySession
from jepsen_tpu.history import History, op
from jepsen_tpu.store import format as fmt

SEED = 1337


class RecordingNet(jnet.Net):
    """Counts heal/drop calls; never touches a real network."""

    def __init__(self):
        self.heals = 0
        self.drops = 0

    def drop(self, test, src, dest):
        self.drops += 1

    def drop_all(self, test, grudge):
        self.drops += 1

    def heal(self, test):
        self.heals += 1

    def slow(self, *a, **kw):
        pass

    def flaky(self, *a, **kw):
        pass

    def fast(self, *a, **kw):
        pass

    def shape(self, *a, **kw):
        pass


def assert_invariants(test, tmp_path, expect_results=True):
    """The four run-level chaos invariants over a finished run."""
    # 2. history well-formed
    problems = chaos.validate_history(test["history"])
    assert problems == []
    # 4. store validates: the op log is fully intact (no torn tail —
    # the writer sealed it) and every op reads back
    d = store.path(test)
    log = d / "history.jlog"
    assert fmt._valid_prefix_end(log) == log.stat().st_size
    assert len(list(fmt.read_ops(log))) == len(test["history"])
    if expect_results:
        assert (d / "results.json").exists()
        with open(d / "results.json") as f:
            results = json.load(f)
        # 5. analysis succeeded or degraded cleanly
        assert results["valid?"] in (True, False, "unknown")


def chaos_run(tmp_path, name, *, client_rates=None, nemesis=None,
              net=None, nodes=3, ops=120, quarantine=False,
              checker_=None):
    state = testing.AtomState()
    inner = testing.AtomClient(state, latency_s=0.0005)
    test = testing.noop_test()
    test.update(
        name=name, store_base=str(tmp_path),
        nodes=[f"n{i}" for i in range(1, nodes + 1)],
        concurrency=nodes,
        net=net if net is not None else RecordingNet(),
        db=testing.AtomDB(state),
        client=chaos.ChaosClient(inner, seed=SEED,
                                 rates=client_rates),
        checker=checker_ or checker.compose({
            "stats": checker.stats(),
            "exceptions": checker.unhandled_exceptions()}),
        generator=gen.clients(
            gen.limit(ops, lambda: {"f": "read"}),
            gen.limit(6, gen.cycle(gen.phases(
                gen.sleep(0.02), {"type": "info", "f": "start"},
                gen.sleep(0.02), {"type": "info", "f": "stop"})))))
    if nemesis is not None:
        test["nemesis"] = nemesis
    if quarantine:
        test["quarantine?"] = {"threshold": 2, "cooldown_s": 60}
    return core.run(test)  # invariant 1: this returns


class TestChaosClientRun:
    def test_seeded_chaos_run_keeps_invariants(self, tmp_path):
        telemetry.reset()
        t = chaos_run(tmp_path, "chaos-client",
                      nemesis=jnemesis.partition_random_node())
        assert_invariants(t, tmp_path)
        # the seed must actually have injected faults, or this suite
        # tests nothing
        tally = t["client"].tally
        assert sum(tally.values()) > 0
        # injected faults surfaced honestly in the history
        types = {o.type for o in t["history"]}
        assert "ok" in types

    def test_chaos_faults_map_to_honest_completions(self, tmp_path):
        t = chaos_run(tmp_path, "chaos-honest", client_rates={
            "drop-connection": 0.2, "command-timeout": 0.2,
            "exception": 0.1})
        assert_invariants(t, tmp_path)
        hist = t["history"]
        tally = t["client"].tally
        fails = sum(1 for o in hist if o.type == "fail")
        infos = sum(1 for o in hist if o.type == "info"
                    and isinstance(o.process, int))
        # drops became definite :fail; timeouts/exceptions :info
        assert fails >= tally["drop-connection"] > 0
        assert infos >= tally["command-timeout"] > 0
        assert tally["exception"] > 0

    def test_nemesis_teardown_crash_still_heals(self, tmp_path):
        """Invariant 3: a dead nemesis can't leak partitions — the
        final heal in run_case fires anyway."""
        net = RecordingNet()
        nem = chaos.CrashingNemesis(jnemesis.partition_halves())
        telemetry.reset()
        t = chaos_run(tmp_path, "chaos-nem-crash", net=net, nemesis=nem)
        assert_invariants(t, tmp_path)
        assert net.heals >= 1  # healed despite the teardown crash
        assert telemetry.get().counters().get(
            "chaos.nemesis-teardown-crashes", 0) >= 1


class TestChaosControlPlane:
    def test_retry_stack_absorbs_transport_chaos(self, monkeypatch,
                                                 tmp_path):
        """Commands through retry(chaos(dummy)) still succeed; the
        chaotic transport shows up as retries, not run failures."""
        monkeypatch.setattr(retry_mod, "BACKOFF_S", 0.001)
        crm = chaos.ChaosRemote(DummyRemote(), seed=SEED, rates={
            "drop-connection": 0.15, "command-timeout": 0.1})
        test = testing.noop_test()
        test.update(nodes=["n1", "n2", "n3"],
                    remote=retry_mod.RetryingRemote(crm), ssh={})
        test = control.open_sessions(test)
        try:
            for _ in range(10):
                outs = control.on_nodes(
                    test, lambda t, n: control.exec_("true"))
                assert set(outs) == {"n1", "n2", "n3"}
        finally:
            control.close_sessions(test)
        assert sum(crm.tally.values()) > 0

    def test_quarantine_dead_node_run_degrades(self, tmp_path):
        """A node dead from the start: ops crash to :info, the run
        finishes with a :degraded marker instead of aborting."""

        class DeadNodeRemote(Remote):
            def connect(self, spec):
                if spec.get("host") == "n2":
                    raise TransportError("connection refused",
                                         node="n2")
                return DummySession(spec.get("host"))

        class CmdClient(jclient.Client):
            def __init__(self, node=None):
                self.node = node

            def open(self, test, node):
                return CmdClient(node)

            def invoke(self, test, op_):
                with control.with_session(test, self.node):
                    control.exec_("true")
                return op_.copy(type="ok")

        test = testing.noop_test()
        test.update(name="chaos-quarantine", store_base=str(tmp_path),
                    nodes=["n1", "n2"], concurrency=2,
                    remote=DeadNodeRemote(), ssh={},
                    net=RecordingNet(),
                    client=CmdClient(), checker=checker.stats(),
                    generator=gen.clients(
                        gen.limit(24, lambda: {"f": "read"})))
        test["quarantine?"] = {"threshold": 2, "cooldown_s": 60}
        t = core.run(test)
        res = t["results"]
        assert res["valid?"] in (True, False, "unknown")
        assert res["degraded"]["quarantined-nodes"] == ["n2"]
        assert chaos.validate_history(t["history"]) == []
        # n1's ops succeeded; n2's crashed fast to :info or failed
        assert any(o.type == "ok" for o in t["history"])

    def test_degraded_client_open_closes_half_open_client(
            self, tmp_path):
        """open() succeeded, then setup() died with a transport error
        under quarantine: the half-open client is closed, not leaked
        for the rest of the (continuing) run."""
        closed = []

        class HalfDeadClient(jclient.Client):
            def __init__(self, node=None):
                self.node = node

            def open(self, test, node):
                return HalfDeadClient(node)

            def setup(self, test):
                if self.node == "n2":
                    raise TransportError("died in setup", node="n2")

            def invoke(self, test, op_):
                return op_.copy(type="ok")

            def close(self, test):
                closed.append(self.node)

        test = testing.noop_test()
        test.update(name="chaos-half-open", store_base=str(tmp_path),
                    nodes=["n1", "n2"], concurrency=2,
                    net=RecordingNet(),
                    client=HalfDeadClient(), checker=checker.stats(),
                    generator=gen.clients(
                        gen.limit(8, lambda: {"f": "read"})))
        test["quarantine?"] = {"threshold": 2, "cooldown_s": 60}
        t = core.run(test)
        assert "n2" in closed
        assert t["results"]["valid?"] in (True, False, "unknown")

    def test_teardown_real_bug_not_masked_by_dead_node(self):
        """Every node's teardown is attempted: a dead node's transport
        failure must not hide a genuine teardown bug on a live one
        (on_nodes alone surfaces only the FIRST node's failure)."""
        from jepsen_tpu import util

        test = testing.noop_test()
        test.update(nodes=["n1", "n2"],
                    sessions={"n1": DummySession("n1"),
                              "n2": DummySession("n2")},
                    health=object())  # quarantine active

        def node_fn(t, n):
            if n == "n1":
                raise TransportError("down", node="n1")
            raise AssertionError("real teardown bug")

        with pytest.raises(util.RealPmapError) as e:
            core._teardown_tolerantly(test, "db", node_fn)
        kinds = {type(x) for x in e.value.errors}
        assert AssertionError in kinds
        assert TransportError in kinds

    def test_teardown_all_transport_degrades(self):
        test = testing.noop_test()
        test.update(nodes=["n1", "n2"],
                    sessions={"n1": DummySession("n1"),
                              "n2": DummySession("n2")},
                    health=object())
        telemetry.reset()

        def node_fn(t, n):
            raise TransportError("down", node=n)

        core._teardown_tolerantly(test, "db", node_fn)  # must not raise
        assert telemetry.get().counters()[
            "core.degraded-teardowns"] == 1

    def test_transport_failure_classification(self):
        """Raw network-errno OSErrors (EHOSTUNREACH et al., which
        Python does NOT map onto ConnectionError) degrade under
        quarantine; local misconfiguration never does."""
        import errno

        assert core._transport_failure(
            OSError(errno.EHOSTUNREACH, "no route to host"))
        assert core._transport_failure(ConnectionRefusedError())
        assert core._transport_failure(TransportError("down"))
        assert not core._transport_failure(
            FileNotFoundError(2, "missing client binary"))
        assert not core._transport_failure(TypeError("client bug"))

    def test_breaker_opens_and_heals(self):
        from jepsen_tpu.control import health

        b = health.CircuitBreaker("n1", threshold=2, cooldown_s=0.05)
        assert b.admit()
        b.failure()
        assert not b.is_open
        b.failure()
        assert b.is_open
        assert not b.admit()  # quarantined: fail fast
        import time
        time.sleep(0.06)
        assert b.admit()       # half-open probe
        assert not b.admit()   # only ONE probe
        b.success()
        assert not b.is_open
        assert b.admit()

    def test_lazy_connect_does_not_heal_circuit(self, monkeypatch):
        """The default stack's RetryingRemote.connect is lazy (no
        network I/O): it must not count as a breaker success, or a
        dead node's failure count resets on every per-op reconnect
        and the circuit never opens."""
        monkeypatch.setattr(retry_mod, "BACKOFF_S", 0.001)

        from jepsen_tpu.control import health

        class Dead(Remote):
            def connect(self, spec):
                class S(DummySession):
                    def execute(self, action):
                        raise TransportError("down")

                return S(spec.get("host"))

        reg = health.HealthRegistry(threshold=3)
        guarded = health.GuardedRemote(
            retry_mod.RetryingRemote(Dead(), budget_limit=2), reg)
        lazy = health.LazyConnectSession(guarded, {"host": "n1"})
        for _ in range(4):
            with pytest.raises(TransportError):
                lazy.execute(Action(cmd="true"))
        assert reg.quarantined() == ["n1"]

    def test_half_open_probe_frees_on_non_transport_error(self):
        """A probe that dies locally (OSError, a caller bug — not a
        transport verdict) must free the probe slot; otherwise the
        circuit wedges half-open and the node never heals."""
        import time

        from jepsen_tpu.control import health

        class LocalBoom(DummySession):
            def execute(self, action):
                raise OSError("disk full")

        b = health.CircuitBreaker("n1", threshold=1, cooldown_s=0.01)
        b.failure()
        assert b.is_open
        time.sleep(0.02)
        sess = health.GuardedSession(LocalBoom("n1"), b)
        with pytest.raises(OSError):
            sess.execute(Action(cmd="true"))
        assert b.is_open  # no verdict on the node: still quarantined
        assert b.admit()  # but the NEXT probe is admitted, not wedged

    def test_guarded_remote_counts_only_transport_errors(self):
        from jepsen_tpu.control import health

        class ExitingSession(DummySession):
            def execute(self, action):
                return Result(exit=1, out="", err="nope",
                              cmd=action.cmd)

        class R(Remote):
            def connect(self, spec):
                return ExitingSession(spec.get("host"))

        reg = health.HealthRegistry(threshold=1)
        sess = health.GuardedRemote(R(), reg).connect({"host": "n1"})
        for _ in range(5):
            sess.execute(Action(cmd="false"))  # nonzero exit, no raise
        assert reg.quarantined() == []  # command failures never count


class TestRetryBudget:
    def test_budget_exhaustion_fails_fast(self, monkeypatch):
        monkeypatch.setattr(retry_mod, "BACKOFF_S", 0.001)

        class AlwaysDown(Remote):
            def __init__(self):
                self.attempts = 0

            def connect(self, spec):
                outer = self

                class S(DummySession):
                    def execute(self, action):
                        outer.attempts += 1
                        raise TransportError("down")

                return S(spec.get("host"))

        down = AlwaysDown()
        remote = retry_mod.RetryingRemote(down, budget_limit=3)
        sess = remote.connect({"host": "n1"})
        telemetry.reset()
        with pytest.raises(TransportError):
            sess.execute(Action(cmd="true"))
        # initial try + 3 budgeted retries, NOT the full 5 retries
        first = down.attempts
        assert first == 4
        # budget is spent: the next command gets exactly one attempt
        with pytest.raises(TransportError):
            sess.execute(Action(cmd="true"))
        assert down.attempts == first + 1
        assert telemetry.get().counters()[
            "control.retry.budget-exhausted"] >= 1

    def test_decorrelated_jitter_bounds(self):
        import random

        rng = random.Random(7)
        s = retry_mod.BACKOFF_S
        for _ in range(100):
            s2 = retry_mod.decorrelated_jitter(s, rng=rng)
            assert retry_mod.BACKOFF_S <= s2 <= retry_mod.BACKOFF_CAP_S
            s = s2

    def test_budget_refunds_on_success(self, monkeypatch):
        """Alternating blip/success forever must never exhaust a small
        budget: each success refunds it, so a multi-hour run's nemesis
        windows can't starve late-run retries."""
        monkeypatch.setattr(retry_mod, "BACKOFF_S", 0.001)

        class Flaky(Remote):
            def __init__(self):
                self.calls = 0

            def connect(self, spec):
                outer = self

                class S(DummySession):
                    def execute(self, action):
                        outer.calls += 1
                        if outer.calls % 2 == 1:
                            raise TransportError("blip")
                        return Result(0, "ok", "", action.cmd)

                return S(spec.get("host"))

        remote = retry_mod.RetryingRemote(Flaky(), budget_limit=2)
        sess = remote.connect({"host": "n1"})
        for _ in range(10):  # 10 blips > budget 2, refunded each time
            assert sess.execute(Action(cmd="x")).out == "ok"
        assert not sess.budget.exhausted

    def test_budget_not_shared_across_sessions(self, monkeypatch):
        monkeypatch.setattr(retry_mod, "BACKOFF_S", 0.001)

        class Flaky(Remote):
            calls = 0

            def connect(self, spec):
                outer = self

                class S(DummySession):
                    def execute(self, action):
                        Flaky.calls += 1
                        if Flaky.calls % 2 == 1:
                            raise TransportError("blip")
                        return Result(0, "ok", "", action.cmd)

                return S(spec.get("host"))

        remote = retry_mod.RetryingRemote(Flaky(), budget_limit=2)
        s1 = remote.connect({"host": "n1"})
        s2 = remote.connect({"host": "n2"})
        assert s1.execute(Action(cmd="x")).out == "ok"
        assert s2.execute(Action(cmd="x")).out == "ok"
        assert s1.budget is not s2.budget


class TestCheckerTimeout:
    def test_hung_checker_degrades_to_unknown(self):
        import time

        class Hung(checker.Checker):
            def check(self, test, hist, opts=None):
                time.sleep(30)

        hist = History([op(type="invoke", process=0, f="read",
                           value=None),
                        op(type="ok", process=0, f="read", value=1)])
        telemetry.reset()
        c = checker.compose({"hung": Hung(), "stats": checker.stats()})
        res = c.check({"checker_timeout_s": 0.2}, hist, {})
        assert res["hung"]["valid?"] == "unknown"
        assert "timed out" in res["hung"]["error"]
        assert res["stats"]["valid?"] is True  # others still ran
        assert res["valid?"] == "unknown"
        assert telemetry.get().counters()["checker.timeouts"] >= 1

    def test_none_returning_checker_is_not_a_timeout(self):
        res = checker.check_safe(checker.noop(), {}, History([]),
                                 timeout_s=5.0)
        assert res is None


class TestDegradationLadder:
    def _hist(self, valid=True):
        ops = [op(index=0, time=0, type="invoke", process=0, f="write",
                  value=1),
               op(index=1, time=1, type="ok", process=0, f="write",
                  value=1),
               op(index=2, time=2, type="invoke", process=1, f="read",
                  value=None),
               op(index=3, time=3, type="ok", process=1, f="read",
                  value=1 if valid else 99)]
        return History(ops)

    def test_forced_oom_walks_ladder_to_host(self, monkeypatch):
        from jepsen_tpu.checker import models
        from jepsen_tpu.tpu import wgl

        m = models.register(0)
        want = wgl.analysis(m, self._hist())
        assert want["valid?"] is True

        def boom(*a, **kw):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

        monkeypatch.setattr(wgl, "_launch", boom)
        telemetry.reset()
        got = wgl.analysis(m, self._hist())
        assert got["valid?"] is want["valid?"]  # identical verdict
        assert got["degradation"][-1] == "host-fallback"
        assert "host-floor" in got["degradation"]
        c = telemetry.get().counters()
        assert c["wgl.ladder.oom"] >= 1
        assert c["wgl.ladder.host-floor"] >= 1

    def test_forced_oom_invalid_verdict_parity(self, monkeypatch):
        from jepsen_tpu.checker import models
        from jepsen_tpu.tpu import wgl

        m = models.register(0)
        want = wgl.analysis(m, self._hist(valid=False))
        assert want["valid?"] is False

        monkeypatch.setattr(wgl, "_launch", lambda *a, **kw: (_ for _ in
                            ()).throw(RuntimeError("RESOURCE_EXHAUSTED")))
        got = wgl.analysis(m, self._hist(valid=False))
        assert got["valid?"] is False
        assert "degradation" in got

    def test_compile_failure_classified(self):
        from jepsen_tpu.tpu import wgl

        class XlaRuntimeError(Exception):
            pass

        assert wgl.device_error_kind(
            RuntimeError("RESOURCE_EXHAUSTED: oom")) == "oom"
        assert wgl.device_error_kind(
            XlaRuntimeError("error during compilation")) == "compile"
        # XlaRuntimeError is ALSO jax's runtime-error type: an
        # execute-time failure is 'device' (degradable but loud),
        # not 'compile'
        assert wgl.device_error_kind(
            XlaRuntimeError("INTERNAL: device lost")) == "device"
        assert wgl.device_error_kind(ValueError("plain bug")) is None
        assert wgl.device_error_kind(wgl.RangeError("big")) is None

    def test_compile_failure_skips_batch_halving(self, monkeypatch):
        """A compile failure is deterministic for the shape: the
        batch-halving rung is skipped (each sub-batch would just
        re-fail compilation) and the ladder goes width-halve ->
        host floor."""
        from jepsen_tpu.checker import models
        from jepsen_tpu.tpu import encode, wgl

        m = models.register(0)
        encs = [encode.encode(m, self._hist()) for _ in range(4)]
        calls = {"n": 0}

        def boom(*a, **kw):
            calls["n"] += 1
            raise RuntimeError("error during compilation")

        monkeypatch.setattr(wgl, "_launch", boom)
        telemetry.reset()
        res = wgl.check_batch(encs)
        assert list(res) == [wgl.UNKNOWN] * 4
        # one failed attempt per width (32 -> 16 -> 8), never one per
        # halved sub-batch
        assert calls["n"] == 3
        c = telemetry.get().counters()
        assert "wgl.ladder.batch-halved" not in c
        assert c["wgl.ladder.width-halved"] >= 1

    def test_ladder_fork_keeps_own_provenance(self):
        """The scope's consecutive-dedup must not swallow a rung that
        belongs to a DIFFERENT result: chunk B's OOM right after chunk
        A's still lands in chunk B's own (forked) list."""
        from jepsen_tpu.tpu import wgl

        with wgl._ladder_scope() as steps:
            wgl._ladder_note("oom")          # chunk A's failure
            with wgl._ladder_fork() as sub:  # chunk B's own view
                wgl._ladder_note("oom")
                wgl._ladder_note("host-floor")
            assert sub == ["oom", "host-floor"]
            assert steps == ["oom", "host-floor"]  # merged, deduped

    def test_batch_halving_isolates_failure(self, monkeypatch):
        """A batch whose first launch OOMs splits and retries; the
        halves succeed on the real kernel."""
        from jepsen_tpu.checker import models
        from jepsen_tpu.tpu import encode, wgl

        m = models.register(0)
        encs = [encode.encode(m, self._hist()) for _ in range(4)]
        calls = {"n": 0}
        real = wgl._launch

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: oom")
            return real(*a, **kw)

        monkeypatch.setattr(wgl, "_launch", flaky)
        res = wgl.check_batch(encs)
        assert list(res) == [wgl.VALID] * 4
        assert calls["n"] >= 3  # failed once, then the two halves

    def test_streamed_degradation_stamped_per_chunk(self, monkeypatch):
        """Only the chunk the device actually failed on carries the
        rungs; verdicts produced by the healthy device stay clean."""
        from jepsen_tpu.checker import models
        from jepsen_tpu.tpu import wgl

        m = models.register(0)
        hists = [self._hist() for _ in range(4)]
        calls = {"n": 0}
        real = wgl._launch

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 2:  # the SECOND chunk's launch OOMs
                raise RuntimeError("RESOURCE_EXHAUSTED: oom")
            return real(*a, **kw)

        monkeypatch.setattr(wgl, "_launch", flaky)
        res = wgl.analysis_batch_streamed(m, hists, chunk=2)
        assert [r["valid?"] for r in res] == [True] * 4
        assert "degradation" not in res[0]
        assert "degradation" not in res[1]
        assert "degradation" in res[2]
        assert "degradation" in res[3]

    def test_streamed_forced_host_keeps_every_verdict(self, monkeypatch):
        """A dead device changes no verdict of a streamed ensemble with
        crashed ops and an invalid member: every history falls back to
        the host search and says so."""
        from jepsen_tpu.checker import models
        from jepsen_tpu.tpu import synth, wgl

        m = models.cas_register()
        hists = [synth.register_history(40, n_procs=4, seed=2000 + i,
                                        crash_p=0.1) for i in range(6)]
        hists[3], _bad = synth.corrupt_register_history(hists[3],
                                                        at_frac=0.5)
        want = wgl.analysis_batch_streamed(m, hists, chunk=2)
        assert [r["valid?"] for r in want] == [True] * 3 + [False] \
            + [True] * 2

        def boom(*a, **kw):
            raise RuntimeError("RESOURCE_EXHAUSTED: forced device "
                               "failure")

        monkeypatch.setattr(wgl, "_launch", boom)
        got = wgl.analysis_batch_streamed(m, hists, chunk=2)
        assert [r["valid?"] for r in got] == [r["valid?"] for r in want]
        assert all(r["degradation"] == ["oom"]
                   and "host-fallback" in r["analyzer"] for r in got)

    def test_elle_device_failure_falls_back_to_host(self, monkeypatch):
        from jepsen_tpu.tpu import elle, elle_device

        ops = []
        for i in range(3):
            ops.append(op(index=2 * i, time=2 * i, type="invoke",
                          process=i, f="txn",
                          value=[["append", "x", i]]))
            ops.append(op(index=2 * i + 1, time=2 * i + 1, type="ok",
                          process=i, f="txn",
                          value=[["append", "x", i]]))
        hist = History(ops)
        want = elle.check_list_append(hist, {"engine": "host"})

        def boom(h):
            raise RuntimeError("RESOURCE_EXHAUSTED: device oom")

        monkeypatch.setattr(elle_device, "analyze_list_append", boom)
        telemetry.reset()
        got = elle.check_list_append(hist, {"engine": "device"})
        assert got["valid?"] == want["valid?"]
        assert got["degradation"] == ["oom", "host-fallback"]
        assert telemetry.get().counters()[
            "elle.ladder.host-fallback"] == 1


class TestChaosCertificates:
    """ISSUE-10 satellite: harness fault injection never yields a
    verdict whose certificate fails to validate — an honest
    `certificate: absent` is allowed (host floors, non-replayable
    models), a validating-but-wrong proof never is. The stamp itself
    runs inside core.analyze; these tests assert its outcome under
    seeded chaos."""

    def _cert_checker(self):
        from jepsen_tpu.checker import models

        return checker.compose({
            "linear": checker.linearizable(
                {"model": models.cas_register(0)}),
            "stats": checker.stats()})

    @staticmethod
    def assert_certificates_honest(results):
        from jepsen_tpu.tpu import certify

        seen = 0
        for path, res in certify.iter_certificates(results):
            seen += 1
            cert = res["certificate"]
            certify.validate_schema(cert)
            # the invariant: certified XOR honestly absent — never a
            # proof that failed validation
            if "absent" in cert:
                continue
            assert res.get("certified") is True, \
                (path, res.get("certificate-error"))
        assert seen >= 1, "no certificates to check — suite is moot"

    def test_chaos_run_certificates_validate(self, tmp_path):
        telemetry.reset()
        t = chaos_run(tmp_path, "chaos-certs", client_rates={
            "drop-connection": 0.15, "command-timeout": 0.1,
            "exception": 0.05}, checker_=self._cert_checker())
        assert_invariants(t, tmp_path)
        assert sum(t["client"].tally.values()) > 0  # faults really flew
        self.assert_certificates_honest(t["results"])

    def test_forced_device_failure_keeps_proofs_honest(
            self, tmp_path, monkeypatch):
        """The degradation ladder's host floor still produces a
        verdict whose certificate validates (extraction is host-side
        and kernel-independent) — or says absent; never a bad proof."""
        from jepsen_tpu.tpu import wgl

        def boom(*a, **kw):
            raise RuntimeError("RESOURCE_EXHAUSTED: chaos-forced oom")

        monkeypatch.setattr(wgl, "_launch", boom)
        telemetry.reset()
        t = chaos_run(tmp_path, "chaos-certs-floor",
                      checker_=self._cert_checker())
        assert_invariants(t, tmp_path)
        res = t["results"]
        assert res["linear"].get("degradation"), \
            "the ladder never walked — forcing failed"
        self.assert_certificates_honest(res)


class TestRecoverableFlag:
    def test_live_pid_suppresses_recoverable(self, tmp_path):
        """A quiet-but-running test (single checker computing for
        minutes without touching a file) must not be advertised as
        crashed; only a dead control process is recoverable."""
        import time as _time

        from jepsen_tpu import web

        td = tmp_path / "demo" / "t1"
        td.mkdir(parents=True)
        (td / "history.jlog").write_text("x")
        old = _time.time() - 3600
        os.utime(td / "history.jlog", (old, old))
        (td / "run.pid").write_text(str(os.getpid()))  # alive: us
        assert not web._looks_recoverable(td)
        (td / "run.pid").write_text("999999999")  # no such pid
        assert web._looks_recoverable(td)
        (td / "run.pid").unlink()  # old store: mtime heuristic
        assert web._looks_recoverable(td)

    def test_run_writes_pid_marker(self, tmp_path):
        t = chaos_run(tmp_path, "pid-marker", ops=8)
        d = store.path(t)
        assert int((d / "run.pid").read_text()) == os.getpid()


class TestValidateHistory:
    def test_clean_history_passes(self):
        hist = [op(index=0, type="invoke", process=0, f="r",
                   value=None),
                op(index=1, type="ok", process=0, f="r", value=1)]
        assert chaos.validate_history(hist) == []

    def test_detects_orphan_completion(self):
        hist = History([op(type="ok", process=0, f="r", value=1)])
        assert any("without invocation" in p
                   for p in chaos.validate_history(hist))

    def test_detects_f_mismatch(self):
        hist = History([op(type="invoke", process=0, f="r", value=None),
                        op(type="ok", process=0, f="w", value=1)])
        assert any("f=" in p for p in chaos.validate_history(hist))

    def test_detects_double_invoke(self):
        hist = History([op(type="invoke", process=0, f="r", value=None),
                        op(type="invoke", process=0, f="r", value=None)])
        assert any("already in flight" in p
                   for p in chaos.validate_history(hist))
