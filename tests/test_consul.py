"""Consul suite tests: DB command emission via the dummy remote, KV
driver parsing, index-based CAS semantics, and a clusterless
end-to-end register run (mirrors consul/src/jepsen/consul/*.clj)."""

import base64
import json
import threading

from jepsen_tpu import checker as chk
from jepsen_tpu import control, core, independent, net, testing
from jepsen_tpu import generator as gen
from jepsen_tpu.checker import models
from jepsen_tpu.control.core import Action, Result
from jepsen_tpu.control.dummy import DummyRemote
from jepsen_tpu.suites import consul


def getent_responder(node, action):
    if action.cmd.startswith("getent ahostsv4"):
        host = action.cmd.split()[-1]
        n = int(str(host).lstrip("n") or 1)
        return f"10.0.0.{n}    STREAM {host}\n"
    if action.cmd.startswith("stat "):  # nothing cached on the "node"
        return Result(exit=1, out="", err="no such file",
                      cmd=action.cmd)
    if action.cmd.startswith("dirname "):
        return action.cmd.split()[-1].rsplit("/", 1)[0]
    if action.cmd.startswith("ls -A"):
        return "consul"
    return None


def make_test(responder=getent_responder, nodes=("n1", "n2", "n3")):
    # net.ip memoizes per process: an address another test's responder
    # gave "n1" must not stand in for this responder's
    net.clear_ip_cache()
    remote = DummyRemote(responder)
    t = testing.noop_test()
    t.update(nodes=list(nodes), remote=remote,
             sessions={n: remote.connect({"host": n}) for n in nodes})
    return t


def cmds(test, node):
    return [a.cmd for a in test["sessions"][node].log
            if isinstance(a, Action)]


class TestDB:
    def test_primary_bootstraps(self):
        test = make_test()
        db = consul.ConsulDB("1.6.1", http_factory=None)
        with control.with_session(test, "n1"):
            db.setup(test, "n1")
        got = " ; ".join(cmds(test, "n1"))
        assert "consul_1.6.1_linux_amd64.zip" in got
        assert "-bootstrap" in got
        assert "-retry-join" not in got
        assert "-bind 10.0.0.1" in got
        assert "-node n1" in got

    def test_secondary_joins_primary(self):
        test = make_test()
        db = consul.ConsulDB(http_factory=None)
        with control.with_session(test, "n2"):
            db.setup(test, "n2")
        got = " ; ".join(cmds(test, "n2"))
        assert "-retry-join 10.0.0.1" in got
        assert "-bootstrap " not in got
        assert "-bind 10.0.0.2" in got

    def test_teardown_removes_state(self):
        test = make_test()
        db = consul.ConsulDB(http_factory=None)
        with control.with_session(test, "n3"):
            db.teardown(test, "n3")
        got = " ; ".join(cmds(test, "n3"))
        assert "/var/lib/consul" in got
        assert "/opt/consul" in got

    def test_restart_rejoins_never_bootstraps(self):
        test = make_test()
        db = consul.ConsulDB(http_factory=None)
        with control.with_session(test, "n1"):
            db.start(test, "n1")
        got = " ; ".join(cmds(test, "n1"))
        assert "-retry-join" in got and "-bootstrap" not in got


class FakeConsulState:
    """In-memory consul KV speaking the HTTP API's JSON shapes, with
    per-key ModifyIndex and ?cas= semantics (cas=0 creates iff
    absent)."""

    def __init__(self, nodes=("n1", "n2", "n3")):
        self.lock = threading.Lock()
        self.kv: dict = {}  # key -> (value, modify_index)
        self.index = 0
        self.nodes = list(nodes)
        self.requests: list = []  # (method, path, params)

    def request(self, method, path, params=None, body=None):
        self.requests.append((method, path, dict(params or {})))
        with self.lock:
            if path == "/v1/catalog/nodes":
                return 200, json.dumps(
                    [{"Node": n} for n in self.nodes])
            assert path.startswith("/v1/kv/")
            key = path[len("/v1/kv/"):]
            if method == "GET":
                if key not in self.kv:
                    return 404, ""
                value, idx = self.kv[key]
                return 200, json.dumps([{
                    "Key": key, "ModifyIndex": idx,
                    "Value": base64.b64encode(
                        value.encode()).decode()}])
            if method == "PUT":
                params = params or {}
                if "cas" in params:
                    current = self.kv.get(key, (None, 0))[1]
                    if int(params["cas"]) != current:
                        return 200, "false"
                self.index += 1
                self.kv[key] = (body, self.index)
                return 200, "true"
            raise AssertionError(f"unexpected {method} {path}")


class FakeHttpFactory:
    def __init__(self, state=None):
        self.state = state or FakeConsulState()

    def __call__(self, node, consistency=None, timeout=5.0):
        http = consul.ConsulHttp(node, consistency=consistency,
                                 timeout=timeout)
        http.request = self.state.request
        return http


class TestKvDriver:
    def test_get_missing_key(self):
        http = FakeHttpFactory()("n1")
        assert http.get("register/0") == (None, None)

    def test_put_then_get_roundtrips_base64(self):
        f = FakeHttpFactory()
        http = f("n1")
        http.put("register/0", "3")
        value, idx = http.get("register/0")
        assert value == "3" and idx == 1

    def test_cas_success_and_value_mismatch(self):
        f = FakeHttpFactory()
        http = f("n1")
        http.put("k", "1")
        assert http.cas("k", "1", "2") is True
        assert http.get("k")[0] == "2"
        assert http.cas("k", "1", "9") is False  # old value gone
        assert http.get("k")[0] == "2"

    def test_cas_on_missing_key_fails(self):
        http = FakeHttpFactory()("n1")
        assert http.cas("nope", "1", "2") is False

    def test_cas_index_race_loses(self):
        """A concurrent write between the read and the guarded PUT
        bumps ModifyIndex, so the CAS must fail."""
        f = FakeHttpFactory()
        http = f("n1")
        http.put("k", "1")
        real_request = http.request
        raced = {"done": False}

        def racing_request(method, path, params=None, body=None):
            if (method == "PUT" and "cas" in (params or {})
                    and not raced["done"]):
                raced["done"] = True
                real_request("PUT", path, {}, "1")  # sneak a write in
            return real_request(method, path, params, body)

        http.request = racing_request
        assert http.cas("k", "1", "2") is False
        assert f.state.kv["k"][0] == "1"

    def test_consistency_param_threads_through(self):
        f = FakeHttpFactory()
        http = f("n1", consistency="stale")
        http.put("k", "1")
        http.get("k")
        gets = [p for (m, path, p) in f.state.requests if m == "GET"]
        assert all("stale" in p for p in gets)

    def test_await_cluster_ready(self):
        f = FakeHttpFactory(FakeConsulState(nodes=["n1", "n2"]))
        consul.await_cluster_ready(f("n1"), 2, timeout_secs=1)

    def test_await_cluster_ready_times_out(self):
        import pytest

        from jepsen_tpu import util

        f = FakeHttpFactory(FakeConsulState(nodes=["n1"]))
        with pytest.raises(util.Timeout):
            consul.await_cluster_ready(f("n1"), 3, timeout_secs=0.1)


class TestEndToEnd:
    def test_register_workload_clusterless(self):
        factory = FakeHttpFactory()
        opts = {"concurrency": 6, "keys": 2, "ops_per_key": 60,
                "seed": 7}
        w = consul.register_workload(opts)
        w["client"].http_factory = factory

        test = testing.noop_test()
        test.update(
            nodes=["n1", "n2", "n3"], concurrency=6,
            client=w["client"],
            checker=w["checker"],
            generator=gen.clients(gen.stagger(0.0005, w["generator"])))
        test = core.run(test)
        assert test["results"]["valid?"] is True
        # both keys saw ops, with reads, writes and cas attempts
        fs = {op.f for op in test["history"]}
        assert fs == {"read", "write", "cas"}
        keys = {independent.key_(op.value) for op in test["history"]
                if op.value is not None}
        assert {0, 1} <= keys

    def test_phantom_read_detected(self):
        """A fake that returns a never-written value on one read must
        fail the linearizable checker (values are drawn from 0..4, so
        99 is impossible under any ordering)."""

        class PhantomState(FakeConsulState):
            def __init__(self):
                super().__init__()
                self.reads = 0

            def request(self, method, path, params=None, body=None):
                if method == "GET" and path.startswith("/v1/kv/"):
                    self.reads += 1
                    # every GET from the 20th on: a cas's internal
                    # pre-read swallowing a single phantom would hide
                    # the anomaly from the reading threads
                    if self.reads >= 20:
                        return 200, json.dumps([{
                            "Key": path[len("/v1/kv/"):],
                            "ModifyIndex": 1,
                            "Value": base64.b64encode(
                                b"99").decode()}])
                return super().request(method, path, params, body)

        factory = FakeHttpFactory(PhantomState())
        opts = {"concurrency": 4, "keys": 1, "ops_per_key": 80,
                "seed": 3}
        w = consul.register_workload(opts)
        w["client"].http_factory = factory

        test = testing.noop_test()
        test.update(
            nodes=["n1"], concurrency=4,
            client=w["client"],
            checker=w["checker"],
            generator=gen.clients(gen.stagger(0.0005, w["generator"])))
        test = core.run(test)
        assert test["results"]["valid?"] is False


class TestCli:
    def test_test_map_shape(self):
        opts = {"nodes": ["n1", "n2", "n3"], "concurrency": 6,
                "ssh": {"dummy": True}, "time_limit": 5,
                "workload": "register", "seed": 1}
        test = consul.consul_test(opts)
        assert test["name"] == "consul-register"
        assert isinstance(test["db"], consul.ConsulDB)
        assert test["nodes"] == ["n1", "n2", "n3"]

    def test_consistency_opt_reaches_client(self):
        opts = {"nodes": ["n1"], "concurrency": 2,
                "ssh": {"dummy": True}, "consistency": "stale",
                "workload": "register"}
        test = consul.consul_test(opts)
        assert test["client"].consistency == "stale"

    def test_concurrency_one_still_writes(self):
        """Reserve must never starve the write/cas mix (review r3)."""
        factory = FakeHttpFactory()
        opts = {"concurrency": 1, "keys": 1, "ops_per_key": 40,
                "seed": 5}
        w = consul.register_workload(opts)
        w["client"].http_factory = factory
        test = testing.noop_test()
        test.update(nodes=["n1"], concurrency=1,
                    client=w["client"], checker=w["checker"],
                    generator=gen.clients(
                        gen.stagger(0.0005, w["generator"])))
        test = core.run(test)
        assert test["results"]["valid?"] is True
        fs = {op.f for op in test["history"]}
        assert "write" in fs or "cas" in fs

    def test_corrupt_value_crashes_to_info_not_fail(self):
        """A non-integer KV value must not be misfiled as a clean
        network :fail (review r3)."""

        class CorruptState(FakeConsulState):
            def request(self, method, path, params=None, body=None):
                if method == "GET" and path.startswith("/v1/kv/"):
                    return 200, json.dumps([{
                        "Key": path[len("/v1/kv/"):],
                        "ModifyIndex": 1,
                        "Value": base64.b64encode(
                            b"not-a-number").decode()}])
                return super().request(method, path, params, body)

        client = consul.ConsulRegisterClient(
            http_factory=FakeHttpFactory(CorruptState()))
        c = client.open({}, "n1")
        import pytest
        from jepsen_tpu.history import Op

        op = Op(type="invoke", process=0, f="read",
                value=consul.independent.ktuple(0, None))
        with pytest.raises(ValueError):
            c.invoke({}, op)
