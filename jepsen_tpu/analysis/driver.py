"""graftlint driver: registry x rules + concurrency lint + ratchet.

`run_lint()` is the one entry point every surface shares — the
`python -m jepsen_tpu lint` CLI, the web /lint page and tier-1's
baseline gate. It traces every registry entry at its shape buckets
(abstract tracing only: no execution, no devices, CPU-safe), runs
R1-R6 over each trace, AST-audits the host feeder modules (R2) and
the threaded harness modules (C1-C3), and
reports through the house observability fabric (`lint.*` telemetry
counters/gauges).
"""

from __future__ import annotations

import json
import logging
import time
from dataclasses import dataclass, field

from .. import telemetry
from ..tpu import lint as lint_mod
from ..tpu.lint import Finding
from . import concurrency, registry

logger = logging.getLogger(__name__)

# Threaded harness modules under the C1-C3 lock-annotation convention.
CONCURRENCY_MODULE_NAMES = (
    "jepsen_tpu.telemetry",
    "jepsen_tpu.monitor",
    "jepsen_tpu.nodeprobe",
    "jepsen_tpu.interpreter",
    "jepsen_tpu.tpu.profiler",
    # the fleet data plane: every threaded class annotated, C1/C2/C3
    # gated in tier-1 (tests/test_lint.py + tests/test_fleet.py)
    "jepsen_tpu.fleet.scheduler",
    "jepsen_tpu.fleet.server",
    "jepsen_tpu.fleet.client",
    "jepsen_tpu.fleet.flightrec",
    "jepsen_tpu.chaos",
    # checkpoint-and-extend (doc/robustness.md): the store's fault
    # hook and the streaming elle consumer are both threaded
    "jepsen_tpu.tpu.ckpt",
    "jepsen_tpu.tpu.elle",
)


@dataclass
class LintReport:
    findings: list[Finding] = field(default_factory=list)
    traces: list[dict] = field(default_factory=list)  # per-trace meta
    errors: list[dict] = field(default_factory=list)  # entry -> error
    wall_s: float = 0.0
    ratchet: dict | None = None                       # vs a baseline

    def by_rule(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return dict(sorted(out.items()))

    def aggregates(self) -> dict:
        """The numbers the perf ledger tracks toward zero across the
        SPMD rebuild (ROADMAP items 1-2): R3 non-donated bytes, R4
        replicated bytes and unsharded batch-axis count."""
        non_donated = sum(f.cost_bytes or 0 for f in self.findings
                          if f.rule == "R3")
        replicated = sum(f.cost_bytes or 0 for f in self.findings
                         if f.rule == "R4"
                         and f.site.startswith("replicated:"))
        unsharded = sum(1 for f in self.findings
                        if f.rule == "R4"
                        and f.site.startswith("unsharded-axis:"))
        return {"non_donated_bytes": int(non_donated),
                "replicated_bytes": int(replicated),
                "unsharded_axes": int(unsharded),
                "findings": self.by_rule()}

    def to_dict(self) -> dict:
        out = {"findings": [f.to_dict() for f in self.findings],
               "aggregates": self.aggregates(),
               "traces": self.traces, "wall_s": round(self.wall_s, 3)}
        if self.errors:
            out["errors"] = self.errors
        if self.ratchet is not None:
            out["ratchet"] = {
                "new": [f.to_dict() for f in self.ratchet["new"]],
                "baselined": len(self.ratchet["baselined"]),
                "stale": self.ratchet["stale"],
            }
        return out

    def text(self) -> str:
        lines = []
        agg = self.aggregates()
        lines.append(
            f"graftlint: {len(self.findings)} finding(s) across "
            f"{len(self.traces)} trace(s) in {self.wall_s:.2f}s — "
            + (" ".join(f"{r}={n}" for r, n in agg["findings"].items())
               or "clean"))
        lines.append(
            f"  R3 non-donated {agg['non_donated_bytes'] / 1024:.0f} "
            f"KiB · R4 replicated {agg['replicated_bytes'] / 1024:.0f}"
            f" KiB · R4 unsharded axes {agg['unsharded_axes']}")
        new = set()
        if self.ratchet is not None:
            new = {f.key for f in self.ratchet["new"]}
            lines.append(
                f"  baseline: {len(self.ratchet['baselined'])} "
                f"pinned, {len(new)} NEW, "
                f"{len(self.ratchet['stale'])} stale (fixed)")
        for f in sorted(self.findings,
                        key=lambda f: (f.key not in new, f.key)):
            mark = "NEW " if f.key in new else ""
            where = f"  [{f.file}:{f.line}]" if f.file else ""
            lines.append(f"{mark}{f.rule} {f.kernel} "
                         f"{f.site}{where}")
            lines.append(f"    {f.message}")
            if f.hint:
                lines.append(f"    fix: {f.hint}")
        if self.ratchet and self.ratchet["stale"]:
            lines.append("stale baseline entries (fixed — rerun with "
                         "--update to prune):")
            for k in self.ratchet["stale"]:
                lines.append(f"  {k}")
        for e in self.errors:
            lines.append(f"TRACE ERROR {e['entry']}/{e['bucket']}: "
                         f"{e['error']}")
        return "\n".join(lines)


def _concurrency_modules() -> list:
    import importlib

    mods = []
    for name in CONCURRENCY_MODULE_NAMES:
        try:
            mods.append(importlib.import_module(name))
        except ImportError:  # pragma: no cover — partial installs
            logger.warning("concurrency lint: cannot import %s", name)
    return mods


def run_lint(runtime_buckets: bool = False,
             concurrency_lint: bool = True,
             trace_kernels: bool = True,
             full: bool = False,
             rules: set[str] | None = None) -> LintReport:
    """The full pass. runtime_buckets=False keeps the report
    deterministic (the committed baseline's contract); True
    additionally traces the shapes this process actually compiled
    (profiler.shape_buckets()) and runs R5's bucket-cardinality
    cross-check. full=False stops at jax tracing (~0.1s/kernel, the
    tier-1 mode); full=True also lowers each kernel for R4's
    HLO collective scan and XLA cost analysis (seconds)."""
    t0 = time.monotonic()
    rep = LintReport()
    if trace_kernels:
        for entry in registry.entries():
            buckets = list(entry.buckets)
            if runtime_buckets and entry.name == "wgl":
                from ..tpu import profiler

                raw = profiler.shape_buckets().get("wgl", ())
                known = {b["label"] for b in buckets}
                buckets += [b for b in
                            registry.runtime_wgl_buckets(raw)
                            if b["label"] not in known]
            for b in buckets:
                try:
                    trace = entry.trace(b, full=full)
                except Exception as e:  # noqa: BLE001 — keep linting
                    logger.exception("tracing %s/%s failed",
                                     entry.name, b.get("label"))
                    rep.errors.append({"entry": entry.name,
                                       "bucket": b.get("label"),
                                       "error": repr(e)})
                    continue
                rep.findings.extend(lint_mod.run_rules(trace))
                rep.traces.append({
                    "kernel": trace.name, "bucket": trace.bucket,
                    "args_bytes": sum(a.nbytes for a in trace.args),
                    "donated_bytes": sum(a.nbytes for a in trace.args
                                         if a.donated),
                    **{k: trace.cost[k] for k in ("flops",
                                                  "bytes accessed")
                       if k in trace.cost},
                })
        for mod in registry.host_feeder_modules():
            rep.findings.extend(lint_mod.scan_module_dtypes(mod))
        if runtime_buckets:
            from ..tpu import profiler

            rep.findings.extend(lint_mod.runtime_bucket_findings(
                profiler.shape_buckets()))
    if concurrency_lint:
        for mod in _concurrency_modules():
            rep.findings.extend(concurrency.scan_module(mod))
    if rules is not None:
        rep.findings = [f for f in rep.findings if f.rule in rules]
    rep.findings.sort(key=lambda f: (f.rule, f.kernel, f.site))
    rep.wall_s = time.monotonic() - t0
    _mirror_telemetry(rep)
    return rep


def _mirror_telemetry(rep: LintReport) -> None:
    tel = telemetry.get()
    tel.count("lint.runs")
    tel.count("lint.traces", len(rep.traces))
    for rule, n in rep.by_rule().items():
        tel.count(f"lint.findings.{rule}", n)
    agg = rep.aggregates()
    tel.gauge("lint.non-donated-bytes", agg["non_donated_bytes"])
    tel.gauge("lint.replicated-bytes", agg["replicated_bytes"])
    tel.gauge("lint.unsharded-axes", agg["unsharded_axes"])
    tel.gauge("lint.wall-s", round(rep.wall_s, 3))


def gate(report: LintReport, baseline_path,
         rules: set[str] | None = None) -> LintReport:
    """Applies the baseline ratchet to a report (sets .ratchet);
    callers fail on report.ratchet['new']. When the report was
    rule-filtered, pass the same `rules` so pinned findings of OTHER
    rules aren't mislabeled as stale (fixed)."""
    baseline = lint_mod.load_baseline(baseline_path)
    if rules is not None:
        baseline = dict(baseline, findings=[
            e for e in baseline.get("findings", ())
            if e.get("rule") in rules])
    report.ratchet = lint_mod.ratchet(report.findings, baseline)
    telemetry.get().count("lint.new-findings",
                          len(report.ratchet["new"]))
    return report


def main(argv=None) -> int:
    """`python -m jepsen_tpu lint` behind cli.lint_cmd: report, gate
    against --baseline (exit 1 on NEW findings), --update rewrites
    the baseline (pinning current findings, pruning stale keys)."""
    import argparse

    p = argparse.ArgumentParser(prog="lint")
    p.add_argument("--baseline", default=None, metavar="FILE",
                   help="Gate against this committed baseline: only "
                        "NEW findings (not pinned there) fail.")
    p.add_argument("--update", action="store_true",
                   help="Rewrite --baseline with the current "
                        "findings (prunes stale entries).")
    p.add_argument("--json", action="store_true", dest="json_",
                   help="Emit the full report as JSON.")
    p.add_argument("--runtime-buckets", action="store_true",
                   help="Also trace the shape buckets this process "
                        "compiled (non-deterministic; never used for "
                        "the committed baseline).")
    p.add_argument("--full", action="store_true",
                   help="Also LOWER each kernel: R4's HLO collective "
                        "scan + XLA cost analysis (slower; may find "
                        "more than the tracing-only baseline mode).")
    p.add_argument("--rules", default=None, metavar="R1,R2,...",
                   help="Restrict to these rule ids.")
    opts = p.parse_args(argv)
    rules = (set(x.strip() for x in opts.rules.split(","))
             if opts.rules else None)
    if opts.update and rules is not None:
        # a rule-filtered report pins only those rules: writing it
        # would silently drop every other rule's baseline entries,
        # and the next full gate would fail on all of them as NEW
        print("--update with --rules would drop the other rules' "
              "pinned findings; run --update without --rules")
        return 254
    if opts.update and (opts.runtime_buckets or opts.full):
        # the committed baseline's contract is the deterministic
        # default mode: pinning runtime-bucket or lowered-HLO
        # findings leaves entries every default-mode gate (tier-1,
        # web) reports as permanently stale
        print("--update pins the deterministic default mode only; "
              "drop --runtime-buckets/--full")
        return 254
    rep = run_lint(runtime_buckets=opts.runtime_buckets,
                   full=opts.full, rules=rules)
    if opts.update:
        if not opts.baseline:
            print("--update requires --baseline")
            return 254
        lint_mod.write_baseline(opts.baseline, rep.findings)
        print(f"wrote {opts.baseline} "
              f"({len(rep.findings)} finding(s) pinned)")
        return 0
    if opts.baseline:
        gate(rep, opts.baseline, rules=rules)
    if opts.json_:
        print(json.dumps(rep.to_dict(), indent=1))
    else:
        print(rep.text())
    if rep.errors:
        return 2
    if rep.ratchet is not None and rep.ratchet["new"]:
        return 1
    return 0
