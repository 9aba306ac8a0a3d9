"""graftlint: the static-analysis pass over the device kernels.

Covers: one synthetic mini-kernel per rule (R1-R6) asserting
detection, a clean kernel asserting zero findings, baseline-ratchet
semantics (new fails / baselined passes / fixed prunes), the
concurrency lint's positive and negative cases, the production-kernel
sweep (every registry entry traces without error; the committed
baseline gates tier-1 right here), and the profiler's
shape_buckets()/bucket_cardinality satellite.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from jepsen_tpu.analysis import concurrency, driver, registry
from jepsen_tpu.tpu import lint as L


def _jaxpr(fn, *args):
    import jax

    return jax.make_jaxpr(fn)(*args)


def _trace(fn, *args, name="syn", **kw) -> L.KernelTrace:
    return L.KernelTrace(name=name, bucket="t", jaxpr=_jaxpr(fn, *args),
                         **kw)


# ---------------------------------------------------------------------------
# R1 — host sync
# ---------------------------------------------------------------------------

class TestR1HostSync:
    def test_pure_callback_detected(self):
        import jax

        def k(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        fs = L.rule_host_sync(_trace(k, np.ones(4, np.float32)))
        assert [f.rule for f in fs] == ["R1"]
        assert "pure_callback" in fs[0].site
        assert fs[0].file  # jaxpr source provenance

    def test_callback_inside_while_detected(self):
        import jax

        def k(x):
            def body(c):
                return jax.pure_callback(
                    lambda a: a,
                    jax.ShapeDtypeStruct(c.shape, c.dtype), c) + 1

            return jax.lax.while_loop(lambda c: c[0] < 3, body, x)

        fs = L.rule_host_sync(_trace(k, np.zeros(2, np.float32)))
        assert len(fs) == 1  # found through the while body sub-jaxpr


# ---------------------------------------------------------------------------
# R2 — dtype widening
# ---------------------------------------------------------------------------

class TestR2Widening:
    def test_int64_intermediate(self):
        import jax
        import jax.numpy as jnp

        with jax.enable_x64(True):
            def k(x):
                return jnp.sum(x.astype(jnp.int64))

            tr = _trace(k, np.arange(8, dtype=np.int32))
        fs = L.rule_dtype_widening(tr)
        assert any(f.rule == "R2" and "int64" in f.site for f in fs)

    def test_int32_kernel_clean(self):
        import jax.numpy as jnp

        def k(x):
            return jnp.sum(x * 2)

        assert L.rule_dtype_widening(
            _trace(k, np.arange(8, dtype=np.int32))) == []

    def test_host_feeder_ast_scan(self):
        src = ("import numpy as np\n"
               "def feeder(n):\n"
               "    ids = np.arange(n, dtype=np.int64)\n"
               "    return np.zeros(n, dtype='float64')\n"
               "def clean(n):\n"
               "    return np.zeros(n, dtype=np.int32)\n")
        fs = L.scan_source_dtypes(src, "x.py", "x")
        sites = {f.site for f in fs}
        assert sites == {"feeder:int64", "feeder:float64"}
        assert all(f.rule == "R2" and f.line for f in fs)

    def test_class_methods_qualified(self):
        src = ("import numpy as np\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self.x = np.int64(0)\n")
        fs = L.scan_source_dtypes(src, "x.py", "x")
        assert {f.site for f in fs} == {"C.__init__:int64"}


# ---------------------------------------------------------------------------
# R3 — donation
# ---------------------------------------------------------------------------

def _arg(name, nbytes, donated=False):
    return L.ArgSpec(name=name, shape=(nbytes // 4,), dtype="int32",
                     nbytes=nbytes, donated=donated)


class TestR3Donation:
    def test_large_nondonated_flagged(self):
        tr = L.KernelTrace(name="k", bucket="t",
                           args=[_arg("big", 1 << 20),
                                 _arg("tiny", 128)])
        fs = L.rule_donation(tr)
        assert [f.site for f in fs] == ["big"]
        assert fs[0].cost_bytes == 1 << 20

    def test_donated_and_small_pass(self):
        tr = L.KernelTrace(name="k", bucket="t",
                           args=[_arg("big", 1 << 20, donated=True),
                                 _arg("tiny", 128)])
        assert L.rule_donation(tr) == []


# ---------------------------------------------------------------------------
# R4 — sharding readiness
# ---------------------------------------------------------------------------

class TestR4Sharding:
    def test_replicated_large_operand(self):
        tr = L.KernelTrace(
            name="k", bucket="t", args=[_arg("tbl", 1 << 21)],
            partition={"axis": "b", "sharded": ["rows"],
                       "replicated": ["tbl"]})
        fs = L.rule_sharding(tr)
        assert [f.site for f in fs] == ["replicated:tbl"]

    def test_unsharded_batch_axis(self):
        tr = L.KernelTrace(name="k", bucket="t",
                           args=[_arg("rows", 4096)],
                           batch_axes=[("rows", 0, "independent")])
        fs = L.rule_sharding(tr)
        assert [f.site for f in fs] == ["unsharded-axis:rows.0"]

    def test_sharded_axis_passes(self):
        tr = L.KernelTrace(
            name="k", bucket="t", args=[_arg("rows", 4096)],
            partition={"axis": "b", "sharded": ["rows"],
                       "replicated": []},
            batch_axes=[("rows", 0, "independent")])
        assert L.rule_sharding(tr) == []

    def test_hlo_collective_scan(self):
        tr = L.KernelTrace(name="k", bucket="t",
                           hlo_text="... stablehlo.all-gather ...")
        fs = L.rule_sharding(tr)
        assert [f.site for f in fs] == ["collective:all-gather"]


# ---------------------------------------------------------------------------
# R5 — recompile risk
# ---------------------------------------------------------------------------

class TestR5Recompile:
    def test_captured_and_large_consts(self):
        import jax.numpy as jnp

        small = np.arange(4, dtype=np.float32)
        big = np.zeros((200, 200), np.float32)  # 160 KB

        def k(x):
            return x + jnp.sum(big) + small

        fs = L.rule_recompile(_trace(k, np.ones(4, np.float32)))
        sites = {f.site for f in fs}
        assert sites == {"captured-consts", "large-consts"}
        big_f = next(f for f in fs if f.site == "large-consts")
        assert big_f.cost_bytes == big.nbytes

    def test_linear_bucket_policy(self):
        tr = L.KernelTrace(name="k", bucket="t",
                           bucket_policy="linear")
        assert [f.site for f in L.rule_recompile(tr)] == \
            ["bucket-policy"]

    def test_runtime_bucket_cardinality(self):
        buckets = {"leaky": set(range(40)), "ok": {1, 2, 3}}
        fs = L.runtime_bucket_findings(buckets)
        assert [f.kernel for f in fs] == ["leaky"]
        assert fs[0].site == "bucket-cardinality"


# ---------------------------------------------------------------------------
# R6 — while-loop carry bloat
# ---------------------------------------------------------------------------

class TestR6Carry:
    def test_fat_carry_flagged(self):
        import jax

        def k(x):
            def body(c):
                i, a = c
                return i + 1, a * 2

            return jax.lax.while_loop(lambda c: c[0] < 8, body,
                                      (np.int32(0), x))

        # 64*1024 f32 = 256 KiB carry >= the 128 KiB budget
        fs = L.rule_carry(_trace(k, np.ones((64, 1024), np.float32)))
        assert [f.rule for f in fs] == ["R6"]
        assert fs[0].cost_bytes >= 256 * 1024

    def test_lean_carry_passes(self):
        import jax

        def k(x):
            def body(c):
                i, a = c
                return i + 1, a * 2

            return jax.lax.while_loop(lambda c: c[0] < 8, body,
                                      (np.int32(0), x))

        assert L.rule_carry(
            _trace(k, np.ones(16, np.float32))) == []


# ---------------------------------------------------------------------------
# Clean kernel: the whole suite finds nothing
# ---------------------------------------------------------------------------

def test_clean_kernel_zero_findings():
    import jax

    def k(x, y):
        def body(c):
            i, a = c
            return i + 1, a + y

        return jax.lax.while_loop(lambda c: c[0] < 4, body,
                                  (np.int32(0), x))

    args = (np.ones(16, np.float32), np.ones(16, np.float32))
    tr = _trace(k, *args,
                args=[_arg("x", 64, donated=True), _arg("y", 64)],
                bucket_policy="pow2")
    assert L.run_rules(tr) == []


# ---------------------------------------------------------------------------
# Baseline ratchet
# ---------------------------------------------------------------------------

def _finding(site, rule="R3", kernel="k"):
    return L.Finding(rule=rule, kernel=kernel, site=site,
                     message=f"m-{site}")


class TestRatchet:
    def test_new_baselined_stale(self):
        baseline = L.baseline_doc([_finding("a"), _finding("gone")])
        r = L.ratchet([_finding("a"), _finding("b")], baseline)
        assert [f.site for f in r["new"]] == [_finding("b").site]
        assert [f.site for f in r["baselined"]] == ["a"]
        assert r["stale"] == ["R3:k:gone"]

    def test_keys_ignore_line_numbers(self):
        f1 = _finding("a")
        f1.line = 10
        f2 = _finding("a")
        f2.line = 999  # the same finding after unrelated edits
        r = L.ratchet([f2], L.baseline_doc([f1]))
        assert not r["new"] and not r["stale"]

    def test_update_prunes_stale(self, tmp_path):
        p = tmp_path / "b.json"
        L.write_baseline(p, [_finding("a"), _finding("gone")])
        # the fix landed: rewriting pins only what's still found
        L.write_baseline(p, [_finding("a")])
        doc = L.load_baseline(p)
        assert [e["key"] for e in doc["findings"]] == ["R3:k:a"]

    def test_missing_baseline_is_empty(self, tmp_path):
        doc = L.load_baseline(tmp_path / "nope.json")
        assert doc["findings"] == []

    def test_malformed_baseline_raises(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text('{"findings": 3}')
        with pytest.raises(ValueError):
            L.load_baseline(p)

    def test_gate_exit_codes(self, tmp_path):
        rep = driver.LintReport(findings=[_finding("a")])
        p = tmp_path / "b.json"
        L.write_baseline(p, [_finding("a")])
        driver.gate(rep, p)
        assert not rep.ratchet["new"]
        rep2 = driver.LintReport(findings=[_finding("a"),
                                           _finding("b")])
        driver.gate(rep2, p)
        assert [f.site for f in rep2.ratchet["new"]] == ["b"]


# ---------------------------------------------------------------------------
# Concurrency lint
# ---------------------------------------------------------------------------

GOOD = '''
import threading
class Rec:
    _guarded_by_lock = {"_lock": ("_items", "_count")}
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []
        self._count = 0
    def add(self, x):
        with self._lock:
            self._items.append(x)
            self._count += 1
    def _drain_locked(self):
        out = list(self._items)
        self._items.clear()
        return out
    def drain(self):
        with self._lock:
            return self._drain_locked()
'''

BAD = '''
import threading
class Rec:
    _guarded_by_lock = ("_items",)
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []
    def add(self, x):
        self._items.append(x)        # C1: mutator outside lock
    def reset(self):
        self._items = []             # C1: assignment outside lock
    def flush(self):
        self._flush_locked()         # C2: _locked call outside lock
    def _flush_locked(self):
        self._items = []             # ok: *_locked is lock-held
    def deferred(self):
        with self._lock:
            def cb():
                self._items.append(1)   # C1: closure runs later
            return cb
'''


class TestConcurrencyLint:
    def test_compliant_class_clean(self):
        assert concurrency.scan_source(GOOD, "g.py", "g") == []

    def test_violations_detected(self):
        fs = concurrency.scan_source(BAD, "b.py", "b")
        sites = {(f.rule, f.site) for f in fs}
        assert ("C1", "add:_items") in sites
        assert ("C1", "reset:_items") in sites
        assert ("C2", "flush:_flush_locked") in sites
        assert ("C1", "deferred.cb:_items") in sites
        # the *_locked body itself is NOT a finding
        assert not any(f.site.startswith("_flush_locked")
                       for f in fs)

    def test_unannotated_lock_advisory(self):
        src = ("import threading\n"
               "class X:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self.xs = []\n")
        fs = concurrency.scan_source(src, "x.py", "x")
        assert [(f.rule, f.site) for f in fs] == [("C3", "_lock")]
        assert fs[0].severity == "info"

    def test_lockless_class_skipped(self):
        src = "class P:\n    def f(self):\n        self.x = 1\n"
        assert concurrency.scan_source(src, "p.py", "p") == []

    def test_lambda_body_is_a_closure(self):
        src = ("import threading\n"
               "class R:\n"
               "    _guarded_by_lock = ('_xs',)\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self._xs = []\n"
               "    def defer(self):\n"
               "        with self._lock:\n"
               "            return lambda: self._xs.append(1)\n")
        fs = concurrency.scan_source(src, "r.py", "r")
        assert [(f.rule, f.site) for f in fs] == \
            [("C1", "defer.<lambda>:_xs")]

    def test_match_statement_blocks(self):
        src = ("import threading\n"
               "class M:\n"
               "    _guarded_by_lock = ('_xs',)\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self._xs = []\n"
               "    def ok(self, v):\n"
               "        match v:\n"
               "            case 1:\n"
               "                with self._lock:\n"
               "                    self._xs.append(v)\n"
               "    def bad(self, v):\n"
               "        match v:\n"
               "            case 1:\n"
               "                self._xs = [v]\n")
        fs = concurrency.scan_source(src, "m.py", "m")
        assert [(f.rule, f.site) for f in fs] == [("C1", "bad:_xs")]

    def test_production_modules_compliant(self):
        """telemetry/monitor/nodeprobe/profiler carry annotations and
        hold their locks; interpreter keeps worker stats thread-local.
        Any C1/C2 here is a real data race — fix it, don't baseline
        it."""
        fs = []
        for mod in driver._concurrency_modules():
            fs.extend(concurrency.scan_module(mod))
        assert [f for f in fs if f.rule in ("C1", "C2")] == []
        # ... and the convention is actually adopted (no unannotated
        # locks left in the scanned modules)
        assert [f for f in fs if f.rule == "C3"] == []


# ---------------------------------------------------------------------------
# Production sweep + the tier-1 baseline gate
# ---------------------------------------------------------------------------

def _repo_baseline():
    from pathlib import Path

    return Path(__file__).resolve().parent.parent / \
        "lint-baseline.json"


@pytest.fixture(scope="module")
def production_report():
    return driver.run_lint()


class TestProductionSweep:
    def test_every_entry_traces(self, production_report):
        assert production_report.errors == []
        traced = {t["kernel"] for t in production_report.traces}
        assert traced == {"wgl", "wgl-reach", "wgl-segmented",
                          "wgl-sharded", "wgl-single", "wgl-slices",
                          "scc", "scc-single"}

    def test_baseline_gate(self, production_report):
        """THE tier-1 ratchet: a change that introduces a finding not
        pinned in lint-baseline.json fails here. Fix the finding, or
        — for a deliberate, justified regression — re-pin with
        `python -m jepsen_tpu lint --baseline lint-baseline.json
        --update` and defend it in review."""
        rep = driver.gate(production_report, _repo_baseline())
        assert rep.ratchet["new"] == [], (
            "NEW lint findings vs lint-baseline.json:\n"
            + "\n".join(f"  {f.key}: {f.message}"
                        for f in rep.ratchet["new"]))
        assert rep.ratchet["stale"] == [], (
            "fixed findings still pinned — prune with --update: "
            + ", ".join(rep.ratchet["stale"]))

    def test_rule_breadth_and_provenance(self, production_report):
        """Post-SPMD (ISSUE-15): the sharding/donation rules report
        NOTHING — R3/R4 went to zero with the shard_map rebuild — and
        what remains (the pinned R2 fingerprint, scc's linear bucket
        policy, the R6 carry worklist) still carries file:line
        provenance."""
        rules = {f.rule for f in production_report.findings}
        assert "R3" not in rules and "R4" not in rules, rules
        assert rules, "the R2/R5/R6 worklist vanished? verify, then pin"
        assert all(f.file and f.line
                   for f in production_report.findings)

    def test_all_kernel_args_donated(self, production_report):
        """ISSUE-15 satellite, as the lint itself measures it: the wgl
        packed segment tensors AND the scc edge arrays are donated —
        zero R3 findings, and every kernel trace shows donated
        bytes."""
        assert [f for f in production_report.findings
                if f.rule == "R3"] == []
        for t in production_report.traces:
            assert t["donated_bytes"] > 0, t

    def test_int64_fixes_landed(self, production_report):
        """scc._scc_host and wgl.valid_cut_points now speak int32;
        the only remaining host-feeder int64 is the checkpoint
        fingerprint (pinned: changing it would invalidate every
        existing segment checkpoint)."""
        r2 = [f.site for f in production_report.findings
              if f.rule == "R2"]
        assert r2 == ["_SegmentCheckpoint.__init__:int64"]

    def test_aggregates_shape(self, production_report):
        """The SPMD rebuild drove R3 non-donated bytes, R4 replicated
        bytes and R4 unsharded batch axes all to zero; the lint
        report's aggregates hold them there."""
        agg = production_report.aggregates()
        assert agg["non_donated_bytes"] == 0
        assert agg["replicated_bytes"] == 0
        assert agg["unsharded_axes"] == 0
        assert agg["findings"]

    def test_telemetry_counters(self):
        from jepsen_tpu import telemetry

        tel = telemetry.get()
        before = tel.counters().get("lint.runs", 0)
        driver.run_lint(trace_kernels=False)
        c = tel.counters()
        assert c.get("lint.runs", 0) == before + 1
        assert "lint.non-donated-bytes" in tel.gauges()

    def test_report_json_round_trip(self, production_report):
        doc = json.loads(json.dumps(production_report.to_dict()))
        assert doc["aggregates"]["unsharded_axes"] == 0
        assert doc["aggregates"]["replicated_bytes"] == 0
        assert len(doc["findings"]) == \
            len(production_report.findings)

    def test_cli_gate(self, capsys):
        rc = driver.main(["--baseline", str(_repo_baseline())])
        assert rc == 0
        out = capsys.readouterr().out
        assert "graftlint:" in out and "baseline:" in out

    def test_cli_rules_gate_not_destructive(self, capsys,
                                            tmp_path):
        """--rules narrows BOTH sides of the ratchet (other rules'
        pinned findings are not 'stale'), and --update refuses to
        combine with --rules (it would drop them from the file)."""
        rc = driver.main(["--rules", "R3",
                          "--baseline", str(_repo_baseline())])
        out = capsys.readouterr().out
        assert rc == 0
        assert "stale" not in out.replace("0 stale", "")
        bp = tmp_path / "b.json"
        bp.write_text((_repo_baseline()).read_text())
        rc = driver.main(["--rules", "R3", "--update",
                          "--baseline", str(bp)])
        assert rc == 254
        assert json.loads(bp.read_text()) == \
            json.loads(_repo_baseline().read_text())
        # ... and so do the non-deterministic modes: the committed
        # baseline's contract is the default mode only
        for flag in ("--runtime-buckets", "--full"):
            rc = driver.main([flag, "--update",
                              "--baseline", str(bp)])
            assert rc == 254, flag
        assert json.loads(bp.read_text()) == \
            json.loads(_repo_baseline().read_text())


# ---------------------------------------------------------------------------
# Satellites: profiler shape buckets + runtime cardinality, web
# ---------------------------------------------------------------------------

class TestShapeBuckets:
    def test_accessor_merges_wgl(self):
        from jepsen_tpu.checker import models
        from jepsen_tpu.tpu import profiler, wgl
        from jepsen_tpu.tpu.encode import encode
        from jepsen_tpu.tpu.synth import register_history

        hist = register_history(60, n_procs=3, seed=11)
        enc = encode(models.register(), hist)
        wgl.check_batch([enc])
        buckets = profiler.shape_buckets()
        assert buckets.get("wgl"), buckets
        # runtime bucket tuples translate back into traceable dicts
        rb = registry.runtime_wgl_buckets(buckets["wgl"])
        assert all(b["label"].startswith("rt-") for b in rb)

    def test_bucket_cardinality_gauge(self):
        from jepsen_tpu import telemetry
        from jepsen_tpu.tpu import profiler

        prof = profiler.Profiler()
        tel = telemetry.get()
        prof.bucket_fresh("lintcheck", ("a",))
        prof.bucket_fresh("lintcheck", ("b",))
        prof.bucket_fresh("lintcheck", ("a",))  # cache hit: no growth
        assert tel.gauges().get(
            "profiler.lintcheck.bucket_cardinality") == 2
        # a failed first launch unclaims and retries: the second miss
        # for the SAME bucket must not inflate the cardinality
        prof.bucket_unclaim("lintcheck", ("b",))
        prof.bucket_fresh("lintcheck", ("b",))
        assert tel.gauges().get(
            "profiler.lintcheck.bucket_cardinality") == 2


def test_web_lint_page_and_panel(monkeypatch):
    from jepsen_tpu import web

    # cold cache: the run-page panel must NOT lint inline — it shows
    # a warming placeholder and computes in the background
    web._lint_cache.clear()
    panel = web.lint_panel_html()
    assert "warming" in panel and "/lint" in panel
    # /lint itself is synchronous (the user asked for the report)
    html = web.lint_html()
    assert "graftlint" in html and "R4" in html
    panel = web.lint_panel_html()  # now served from the cache
    assert "/lint" in panel and "unsharded axes" in panel
