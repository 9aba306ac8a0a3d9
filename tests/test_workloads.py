"""Independent key-space machinery + workload bundles, end to end:
every workload runs through the full lifecycle against an in-memory
client and must validate; broken clients must be caught.

Mirrors the reference's approach: generator semantics via deterministic
simulation (generator/test.clj), checker verdicts via real runs against
atom-backed stores (core_test.clj)."""

import pytest

from jepsen_tpu import checker as chk
from jepsen_tpu import core, independent, testing, workloads
from jepsen_tpu import generator as gen
from jepsen_tpu.checker import models
from jepsen_tpu.generator import test_support as sim
from jepsen_tpu.history import History, op


def run_clusterless(client, workload, concurrency=6, nodes=1):
    test = testing.noop_test()
    test.update(nodes=[f"n{i}" for i in range(nodes or 1)],
                concurrency=concurrency, client=client,
                checker=workload["checker"],
                generator=gen.clients(workload["generator"]))
    for k, v in workload.items():
        if k not in ("generator", "checker"):
            test[k] = v
    return core.run(test)


class TestIndependent:
    def test_tuple_helpers(self):
        t = independent.ktuple("x", 5)
        assert independent.key_(t) == "x"
        assert independent.value_(t) == 5
        assert independent.value_(7) == 7

    def test_sequential_generator_simulation(self):
        g = independent.sequential_generator(
            ["a", "b"], lambda k: gen.limit(4, lambda: {"f": "read"}))
        ops = sim.quick(gen.clients(g), sim.n_plus_nemesis_context(2))
        invokes = [o for o in ops if o.type == "invoke"]
        assert len(invokes) == 8
        keys = [o.value[0] for o in invokes]
        assert keys == ["a"] * 4 + ["b"] * 4

    def test_concurrent_generator_simulation(self):
        g = independent.concurrent_generator(
            2, list(range(4)),
            lambda k: gen.limit(6, lambda: {"f": "read"}))
        ops = sim.quick(gen.clients(g), sim.n_plus_nemesis_context(4))
        invokes = [o for o in ops if o.type == "invoke"]
        assert len(invokes) == 24
        # every key gets exactly its 6 ops
        from collections import Counter
        counts = Counter(o.value[0] for o in invokes)
        assert counts == {0: 6, 1: 6, 2: 6, 3: 6}

    def test_subhistories(self):
        hist = History([
            op(type="invoke", process=0, f="read", value=("a", None)),
            op(type="ok", process=0, f="read", value=("a", 1)),
            op(type="invoke", process=1, f="write", value=("b", 2)),
            op(type="ok", process=1, f="write", value=("b", 2))])
        subs = independent.subhistories(hist)
        assert set(subs) == {"a", "b"}
        assert subs["a"][1].value == 1

    def test_independent_checker_batched(self):
        """Per-key histories checked in one device launch via
        Linearizable.check_batch."""
        hist = History([
            op(type="invoke", process=0, f="write", value=("k1", 1)),
            op(type="ok", process=0, f="write", value=("k1", 1)),
            op(type="invoke", process=1, f="read", value=("k1", None)),
            op(type="ok", process=1, f="read", value=("k1", 1)),
            op(type="invoke", process=2, f="write", value=("k2", 3)),
            op(type="ok", process=2, f="write", value=("k2", 3)),
            op(type="invoke", process=3, f="read", value=("k2", None)),
            op(type="ok", process=3, f="read", value=("k2", 9))])  # bad
        c = independent.checker(chk.linearizable(
            {"model": models.cas_register()}))
        res = c.check({}, hist)
        assert res["valid?"] is False
        assert res["failures"] == ["k2"]
        assert res["results"]["k1"]["valid?"] is True


class TestWorkloadsEndToEnd:
    def test_register(self):
        w = workloads.register.workload(
            {"keys": [0, 1], "group_size": 3, "ops_per_key": 40,
             "seed": 5})
        t = run_clusterless(testing.KVClient(testing.KVState()), w,
                            concurrency=6)
        assert t["results"]["valid?"] is True, t["results"]

    def test_bank_valid(self):
        w = workloads.bank.workload({"seed": 1, "ops": 120})
        state = testing.BankState(w["accounts"], initial=10)
        t = run_clusterless(testing.BankClient(state), w)
        assert t["results"]["valid?"] is True, t["results"]["bank"]

    def test_bank_catches_total_violation(self):
        w = workloads.bank.workload({"seed": 2, "ops": 120})
        state = testing.BankState(w["accounts"], initial=11)  # wrong total
        t = run_clusterless(testing.BankClient(state), w)
        assert t["results"]["valid?"] is False

    def test_set_valid_and_lossy(self):
        w = workloads.sets.workload({"ops": 60})
        t = run_clusterless(testing.SetClient(), w)
        assert t["results"]["valid?"] is True, t["results"]

        w = workloads.sets.workload({"ops": 60})
        t = run_clusterless(testing.SetClient(drop_every=10), w)
        assert t["results"]["valid?"] is False
        assert t["results"]["lost-count"] > 0

    def test_set_full(self):
        w = workloads.sets.full_workload({"ops": 80})
        t = run_clusterless(testing.SetClient(), w)
        assert t["results"]["valid?"] in (True, "unknown")

    def test_queue_valid_and_lossy(self):
        w = workloads.queue.workload({"ops": 60})
        t = run_clusterless(testing.QueueClient(), w)
        assert t["results"]["valid?"] is True, t["results"]

        w = workloads.queue.workload({"ops": 60})
        t = run_clusterless(testing.QueueClient(drop_every=7), w)
        assert t["results"]["valid?"] is False

    def test_counter(self):
        w = workloads.counter.workload({"ops": 80, "seed": 3})
        t = run_clusterless(testing.CounterClient(), w)
        assert t["results"]["valid?"] is True, t["results"]

    def test_unique_ids_valid_and_dup(self):
        w = workloads.unique_ids.workload({"ops": 50})
        t = run_clusterless(testing.UniqueIdsClient(), w)
        assert t["results"]["valid?"] is True

        w = workloads.unique_ids.workload({"ops": 50})
        t = run_clusterless(testing.UniqueIdsClient(dup_every=9), w)
        assert t["results"]["valid?"] is False

    def test_long_fork_valid(self):
        w = workloads.long_fork.workload({"ops": 120})
        t = run_clusterless(testing.TxnClient(), w)
        assert t["results"]["valid?"] is True, t["results"]

    def test_txn_append(self):
        w = workloads.txn_append.workload({"ops": 150, "seed": 9})
        t = run_clusterless(testing.TxnClient(), w)
        assert t["results"]["valid?"] is True, t["results"]

    def test_txn_wr(self):
        w = workloads.txn_wr.workload({"ops": 150, "seed": 9})
        t = run_clusterless(testing.TxnClient(), w)
        assert t["results"]["valid?"] is True, t["results"]

    def test_registry_complete(self):
        # Core workload families must stay registered; new families may be
        # added freely (assert subset, not equality, so registrations don't
        # silently break the suite).
        core = {
            "adya-g2", "bank", "causal", "causal-reverse", "counter", "dirty-read",
            "kafka", "long-fork", "monotonic", "sequential", "queue", "register", "set",
            "set-full", "append", "wr", "unique-ids",
            "lock", "fenced-lock", "owner-lock", "reentrant-lock", "semaphore",
            "upsert", "run-coverage", "pages", "multimonotonic", "lost-updates",
            "version-divergence"}
        assert core <= set(workloads.REGISTRY), core - set(workloads.REGISTRY)
        # Every registered workload must build a test map with a generator
        # and a checker from default-ish opts.
        for name, fn in workloads.REGISTRY.items():
            w = fn({"ops": 10})
            assert "generator" in w and "checker" in w, name


class TestBankCheckFast:
    def _hist(self, rows, f="read"):
        from jepsen_tpu.history import History, op
        evs = []
        for i, r in enumerate(rows):
            evs.append(op(type="invoke", process=0, f=f, value=None))
            evs.append(op(type="ok", process=0, f=f, value=r))
        return History(evs)

    def test_fold_path_valid_and_anomalies(self):
        from jepsen_tpu.workloads import bank
        h = self._hist([{0: 5, 1: 5}, {0: 4, 1: 6}])
        assert bank.check_fast(h, 10)["valid?"] is True
        bad = self._hist([{0: 5, 1: 5}, {0: 4, 1: 4}])
        res = bank.check_fast(bad, 10)
        assert res["valid?"] is False
        assert res["first-error"]["type"] == "wrong-total"
        neg = self._hist([{0: -2, 1: 12}])
        res = bank.check_fast(neg, 10)
        assert res["valid?"] is False
        assert res["first-error"]["type"] == "negative-value"
        assert bank.check_fast(neg, 10, negative_ok=True)["valid?"] is True

    def test_matrix_path_matches_fold(self):
        from jepsen_tpu.workloads import bank
        import random
        rng = random.Random(5)
        n_acc = 16  # wide: takes the matrix path
        rows = []
        for _ in range(50):
            vals = [10] * n_acc
            for _ in range(8):
                a, b = rng.sample(range(n_acc), 2)
                amt = rng.randint(1, 5)
                vals[a] -= amt
                vals[b] += amt
            rows.append(dict(enumerate(vals)))
        h = self._hist(rows)
        res = bank.check_fast(h, n_acc * 10, device=False)
        assert res["valid?"] is False  # negatives occur
        assert bank.check_fast(h, n_acc * 10, negative_ok=True,
                               device=False)["valid?"] is True

    def test_empty_is_unknown(self):
        from jepsen_tpu.workloads import bank
        from jepsen_tpu.history import History
        assert bank.check_fast(History([]), 10)["valid?"] == "unknown"


class TestSynthGenerators:
    def test_list_append_history_valid(self):
        from jepsen_tpu.tpu import elle, synth
        h = synth.list_append_history(800, seed=5)
        for engine in ("host", "device"):
            res = elle.check_list_append(h, {"engine": engine})
            assert res["valid?"] is True, (engine, res["anomaly-types"])

    def test_bank_history_valid(self):
        from jepsen_tpu.tpu import synth
        from jepsen_tpu.workloads import bank
        h = synth.bank_history(800, seed=5)
        assert bank.check_fast(h, 80)["valid?"] is True

    def test_bank_history_read_count_matches_fold(self):
        """check_fast counts exactly the ok reads that a plain per-op
        fold sees, and each of them conserves the total."""
        from jepsen_tpu.tpu import synth
        from jepsen_tpu.workloads import bank
        h = synth.bank_history(2000, seed=11)
        res = bank.check_fast(h, 80)
        assert res["valid?"] is True
        reads = [op.value for op in h if op.type == "ok"
                 and op.f == "read" and op.value is not None]
        assert res["read-count"] == len(reads) > 0
        assert all(sum(r.values()) == 80 and min(r.values()) >= 0
                   for r in reads)

    def test_register_history_with_crashes_valid(self):
        from jepsen_tpu.checker import models
        from jepsen_tpu.tpu import synth, wgl
        h = synth.register_history(150, n_procs=4, seed=9, crash_p=0.15)
        a = wgl.analysis(models.cas_register(), h, algorithm="wgl")
        assert a["valid?"] is True, a


class TestSetFullVectorized:
    """The array path must agree with the object path exactly
    (VERDICT r2 weak #6: O(reads x elements) Python loops)."""

    @staticmethod
    def _hist(n_adds, n_reads, lose=(), dup_read=False, seed=0,
              str_values=False):
        import random

        from jepsen_tpu.history import History, op

        rng = random.Random(seed)
        evs = []
        present = []
        idx = 0
        t = 0
        rp = sorted(rng.sample(range(1, n_adds),
                               min(n_reads, n_adds - 1)))

        def val(i):
            return f"e{i}" if str_values else i

        for i in range(n_adds):
            t += 10
            evs.append(op(index=idx, time=t, type="invoke",
                          process=i % 5, f="add", value=val(i)))
            idx += 1
            ok = rng.random() < 0.95
            t += 5
            evs.append(op(index=idx, time=t,
                          type="ok" if ok else "fail",
                          process=i % 5, f="add", value=val(i)))
            idx += 1
            if ok and i not in lose:
                present.append(val(i))
            if rp and i == rp[0]:
                rp.pop(0)
                t += 3
                evs.append(op(index=idx, time=t, type="invoke",
                              process=9, f="read", value=None))
                idx += 1
                t += 3
                vals = list(present)
                if dup_read and vals:
                    vals.append(vals[0])
                evs.append(op(index=idx, time=t, type="ok", process=9,
                              f="read", value=vals))
                idx += 1
        t += 3
        evs.append(op(index=idx, time=t, type="invoke", process=9,
                      f="read", value=None))
        idx += 1
        t += 3
        evs.append(op(index=idx, time=t, type="ok", process=9,
                      f="read", value=list(present)))
        idx += 1
        return History(evs, assign_indices=False)

    def _differential(self, hist):
        from jepsen_tpu import checker as chk

        fast = chk._set_full_results_fast(hist)
        assert fast is not None
        f_rs, f_dups = fast
        s_rs, s_dups = chk._set_full_results_slow(hist)
        assert f_dups == s_dups
        assert len(f_rs) == len(s_rs)
        for a, b in zip(f_rs, s_rs):
            for k in ("element", "outcome", "stable-latency",
                      "lost-latency"):
                assert a[k] == b[k], (a, b)

    def test_clean(self):
        self._differential(self._hist(200, 10, seed=1))

    def test_lost_elements(self):
        self._differential(self._hist(200, 10, lose={50, 51}, seed=2))

    def test_duplicates(self):
        self._differential(self._hist(100, 5, dup_read=True, seed=3))

    def test_no_reads(self):
        self._differential(self._hist(50, 0, seed=4))

    def test_non_int_values_fall_back(self):
        from jepsen_tpu import checker as chk

        hist = self._hist(30, 3, seed=5, str_values=True)
        assert chk._set_full_results_fast(hist) is None
        out = chk.check(chk.set_full(), {}, hist)  # slow path still works
        assert out["valid?"] is True, out

    def test_scale_smoke(self):
        """200k-op history checks in well under the old quadratic
        regime (the 1M-op target is ~5s, measured out-of-band)."""
        import time

        from jepsen_tpu import checker as chk

        hist = self._hist(100_000, 40, lose={777}, seed=6)
        t0 = time.time()
        out = chk.check(chk.set_full(), {}, hist)
        dt = time.time() - t0
        assert out["valid?"] is False
        assert out["lost"] == [777]
        assert dt < 20, f"set-full took {dt:.1f}s on 200k ops"


class TestSetFullEdgeCases:
    def test_adds_but_no_reads_at_all(self):
        """E>0, R==0 must report never-read, not crash (round-3 review
        finding)."""
        from jepsen_tpu import checker as chk
        from jepsen_tpu.history import History, op

        hist = History([
            op(index=0, time=1, type="invoke", process=0, f="add",
               value=1),
            op(index=1, time=2, type="ok", process=0, f="add",
               value=1)], assign_indices=False)
        fast = chk._set_full_results_fast(hist)
        assert fast is not None
        rs, dups = fast
        assert [r["outcome"] for r in rs] == ["never-read"]
        out = chk.check(chk.set_full(), {}, hist)
        assert out["valid?"] == "unknown"

    def test_known_and_last_absent_are_ops(self):
        """Row fields carry the same Op objects as the object path:
        known by read completion when the add never ok'd, last-absent
        as the read invocation (round-3 review finding)."""
        from jepsen_tpu import checker as chk
        from jepsen_tpu.history import History, op

        evs = [
            op(index=0, time=1, type="invoke", process=0, f="add",
               value=7),
            op(index=1, time=2, type="info", process=0, f="add",
               value=7),                                  # never ok'd
            op(index=2, time=3, type="invoke", process=1, f="read",
               value=None),
            op(index=3, time=4, type="ok", process=1, f="read",
               value=[7]),                                # ...but seen
            op(index=4, time=5, type="invoke", process=1, f="read",
               value=None),
            op(index=5, time=6, type="ok", process=1, f="read",
               value=[]),                                 # then gone
        ]
        hist = History(evs, assign_indices=False)
        f_rs, _ = chk._set_full_results_fast(hist)
        s_rs, _ = chk._set_full_results_slow(hist)
        for a, b in zip(f_rs, s_rs):
            assert a["outcome"] == b["outcome"] == "lost"
            assert a["known"] is b["known"]          # the read's ok op
            assert a["last-absent"] is b["last-absent"]


class TestMonotonic:
    """cockroach monotonic.clj equivalents."""

    def _run(self, client, ops=120, concurrency=4):
        from jepsen_tpu import workloads

        w = workloads.monotonic.workload({"ops": ops})
        test = testing.noop_test()
        test.update(nodes=["n1", "n2"], concurrency=concurrency,
                    client=client, checker=w["checker"],
                    generator=gen.clients(gen.phases(
                        gen.stagger(0.0003, w["generator"]),
                        w["final_generator"])))
        return core.run(test)

    def test_healthy_run_valid(self):
        test = self._run(testing.MonotonicClient())
        res = test["results"]
        assert res["valid?"] is True
        assert res["add-count"] > 50 and res["read-count"] > 50
        assert not res["lost"] and not res["duplicates"]

    def test_clock_skew_detected(self):
        # reads sort by sts, so a backwards clock can never violate the
        # (non-strict, ties legal) sts order; it surfaces as values out
        # of order relative to timestamps — monotonic.clj semantics
        test = self._run(testing.MonotonicClient(skew_every=10))
        res = test["results"]
        assert res["valid?"] is False
        assert res["value-reorders"]
        assert not res["order-by-errors"]

    def test_duplicate_insert_detected(self):
        test = self._run(testing.MonotonicClient(dup_every=15))
        res = test["results"]
        assert res["valid?"] is False
        assert res["duplicates"]

    def test_never_read_is_unknown(self):
        from jepsen_tpu import workloads

        w = workloads.monotonic.workload({"ops": 20})
        test = testing.noop_test()
        test.update(nodes=["n1"], concurrency=2,
                    client=testing.MonotonicClient(),
                    checker=w["checker"],
                    generator=gen.clients(
                        gen.stagger(0.0003, w["generator"])))
        test = core.run(test)
        assert test["results"]["valid?"] == "unknown"


class TestSequential:
    """cockroach sequential.clj equivalents."""

    def _run(self, client, ops=200, concurrency=6):
        from jepsen_tpu import workloads

        w = workloads.sequential.workload({"ops": ops, "writers": 3,
                                           "seed": 11})
        test = testing.noop_test()
        test.update(nodes=["n1"], concurrency=concurrency,
                    client=client, key_count=w["key_count"],
                    checker=w["checker"],
                    generator=gen.clients(
                        gen.stagger(0.0003, w["generator"])))
        return core.run(test)

    def test_healthy_run_valid(self):
        test = self._run(testing.SequentialClient())
        res = test["results"]
        assert res["valid?"] is True
        assert res["bad-count"] == 0
        assert res["all-count"] + res["some-count"] + \
            res["none-count"] > 0
        reads = [op for op in test["history"]
                 if op.type == "ok" and op.f == "read"]
        assert reads

    def test_trailing_none_detected(self):
        """Writers that skip a key's first subkey leave later subkeys
        visible without it: sequential consistency violation."""
        test = self._run(
            testing.SequentialClient(hide_first_every=2), ops=300)
        res = test["results"]
        assert res["valid?"] is False
        assert res["bad-count"] > 0

    def test_subkeys_order(self):
        from jepsen_tpu.workloads import sequential as seq

        assert seq.subkeys(3, 7) == ["7_0", "7_1", "7_2"]
        assert seq._trailing_none(["7_2", None]) is True
        assert seq._trailing_none([None, "7_1"]) is False
        assert seq._trailing_none([None, None]) is False

    def test_store_roundtrip_preserves_reads(self, tmp_path,
                                             monkeypatch):
        """A NAMED test round-trips its history through the JSON store
        log (tuples become lists); the checker must still see the
        reads (regression: valid? was 'unknown' from the CLI)."""
        import jepsen_tpu.store as store_mod
        from jepsen_tpu import workloads

        monkeypatch.setattr(store_mod, "BASE", tmp_path / "store")
        w = workloads.sequential.workload({"ops": 100, "writers": 2,
                                           "seed": 3})
        test = testing.noop_test()
        test.update(name="seq-store", nodes=["n1"], concurrency=4,
                    client=testing.SequentialClient(),
                    key_count=w["key_count"], checker=w["checker"],
                    generator=gen.clients(
                        gen.stagger(0.0003, w["generator"])))
        test = core.run(test)
        res = test["results"]
        assert res["valid?"] is True
        assert res["all-count"] + res["some-count"] + \
            res["none-count"] > 0


class TestDirtyRead:
    """elasticsearch dirty_read.clj equivalents."""

    def _run(self, client, ops=200, concurrency=6):
        from jepsen_tpu import workloads

        w = workloads.dirty_read.workload(
            {"ops": ops, "concurrency": concurrency, "seed": 5})
        test = testing.noop_test()
        test.update(nodes=["n1", "n2", "n3"], concurrency=concurrency,
                    client=client, checker=w["checker"],
                    generator=gen.clients(gen.phases(
                        gen.stagger(0.0003, w["generator"]),
                        w["final_generator"])))
        return core.run(test)

    def test_healthy_run_valid(self):
        test = self._run(testing.DirtyReadClient())
        res = test["results"]
        assert res["valid?"] is True
        assert res["read-count"] > 0
        assert res["strong-read-count"] == 6
        assert res["dirty-count"] == 0 and res["lost-count"] == 0

    def test_dirty_read_detected(self):
        """Visible-but-never-committed writes observed by readers must
        surface as dirty."""
        test = self._run(testing.DirtyReadClient(dirty_every=3),
                         ops=400)
        res = test["results"]
        assert res["valid?"] is False
        assert res["dirty-count"] > 0

    def test_lost_write_detected(self):
        test = self._run(testing.DirtyReadClient(lose_every=4),
                         ops=300)
        res = test["results"]
        assert res["valid?"] is False
        assert res["lost-count"] > 0

    def test_no_strong_reads_is_unknown(self):
        from jepsen_tpu import workloads

        w = workloads.dirty_read.workload({"ops": 30,
                                           "concurrency": 3})
        test = testing.noop_test()
        test.update(nodes=["n1"], concurrency=3,
                    client=testing.DirtyReadClient(),
                    checker=w["checker"],
                    generator=gen.clients(
                        gen.stagger(0.0003, w["generator"])))
        test = core.run(test)
        assert test["results"]["valid?"] == "unknown"
