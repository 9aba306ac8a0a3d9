"""graftlint: static analysis of the compiled device kernels.

PR 9's certificates make a *wrong* answer fail by proof; this module
makes a *slow* answer fail by lint. The SPMD rebuild of the wgl /
ensemble / elle kernels (ROADMAP items 1-2) is exactly the kind of
aggressive refactor that silently reintroduces host syncs, full
replication, dtype widening, donation misses and recompile storms —
the failure class AccelSync (PAPERS.md, arXiv:2605.07881) argues must
be caught by *static* verification of accelerator pipeline programs
rather than by benchmarking luck.

The unit of analysis is a KernelTrace: one compiled entry point traced
abstractly (jax.make_jaxpr / Lowered over ShapeDtypeStructs — no
execution, no devices needed) at one of the profiler's real shape
buckets. The rule suite runs over the jaxpr, the lowered HLO text, the
Lowered's argument/donation info, and declared partition metadata:

  R1 host-sync        callback/infeed primitives inside a hot kernel
                      (each one serializes the device on the host)
  R2 dtype-widening   64-bit avals or widening converts in the jaxpr,
                      plus explicit np.int64/float64 in the host
                      feeder modules (the direct input to the
                      int8/int16 state-packing item)
  R3 donation-miss    large non-donated args, measured in bytes
  R4 sharding         large operands replicated across the mesh;
                      embarrassingly-parallel batch axes with no
                      partition rule; collectives in lowered HLO
  R5 recompile-risk   python scalars/large arrays closed over as
                      jaxpr consts; unquantized shape-bucket policies;
                      runtime bucket-cardinality blowups
  R6 carry-bloat      while-loop carries past the byte budget (every
                      byte of carry is serialized through each BFS
                      level)

Findings carry file:line provenance (jaxpr source info where
available), an estimated cost in bytes, and a fix hint. A committed
baseline (lint-baseline.json) pins today's findings so tier-1 fails
only on NEW ones — the ratchet that guards the SPMD refactor. The
kernel registry and driver live in jepsen_tpu.analysis; the threaded
harness modules get their own AST concurrency lint there too.
"""

from __future__ import annotations

import ast
import inspect
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Iterator

# Rule identifiers, in catalog order (doc/static-analysis.md).
RULES = ("R1", "R2", "R3", "R4", "R5", "R6", "C1", "C2", "C3")

# R1: primitives that bounce through the host mid-program. Any one of
# these inside a hot kernel turns an async device dispatch into a
# synchronous host round trip per call.
HOST_SYNC_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed", "host_callback_call",
})

# R2: dtypes that double the memory traffic of the int32 house style.
WIDE_DTYPES = frozenset({"int64", "uint64", "float64"})

# R3: args below this size aren't worth a donation finding (the
# scheduler copies small buffers anyway).
DONATION_MIN_BYTES = 256 * 1024

# R4: a replicated operand below this costs less than the finding.
REPLICATED_MIN_BYTES = 256 * 1024

# R4: collective ops in lowered HLO that force cross-device traffic.
HLO_COLLECTIVES = ("all-gather", "all-to-all", "collective-permute")

# R5: consts bigger than this bloat every compiled executable of the
# bucket (and re-trace per value if the capture isn't stable).
LARGE_CONST_BYTES = 64 * 1024

# R5: runtime cross-check — more compiled buckets than this per kernel
# means the bucketing policy is leaking shapes (one ~seconds compile
# per bucket).
MAX_RUNTIME_BUCKETS = 32

# R6: while-loop carry budget. Every carry byte rides through every
# BFS level; past this the carry itself becomes the bandwidth bill.
CARRY_WARN_BYTES = 128 * 1024


@dataclass
class Finding:
    """One lint finding. `key` (rule:kernel:site) is the stable
    identity the baseline ratchet matches on — deliberately free of
    line numbers, which churn under unrelated edits; file:line ride
    along as provenance only."""

    rule: str
    kernel: str
    site: str
    message: str
    file: str | None = None
    line: int | None = None
    hint: str | None = None
    severity: str = "warn"        # "warn" | "info"
    cost_bytes: int | None = None

    @property
    def key(self) -> str:
        return f"{self.rule}:{self.kernel}:{self.site}"

    def to_dict(self) -> dict:
        d = {"key": self.key, "rule": self.rule, "kernel": self.kernel,
             "site": self.site, "message": self.message,
             "severity": self.severity}
        if self.file:
            d["file"] = self.file
            d["line"] = self.line
        if self.hint:
            d["hint"] = self.hint
        if self.cost_bytes is not None:
            d["cost_bytes"] = int(self.cost_bytes)
        return d


@dataclass
class ArgSpec:
    """One kernel argument as the Lowered saw it."""

    name: str
    shape: tuple
    dtype: str
    nbytes: int
    donated: bool = False


@dataclass
class KernelTrace:
    """One compiled entry point abstractly traced at one shape bucket.

    The registry (jepsen_tpu.analysis.registry) builds these from the
    real jit factories so donation flags, static config and partition
    layout are read off the actual compiled artifacts, not off a
    parallel description that can drift."""

    name: str                     # kernel/registry-entry name
    bucket: str                   # stable bucket label, e.g. B64xM512
    jaxpr: Any = None             # ClosedJaxpr | None
    args: list[ArgSpec] = field(default_factory=list)
    hlo_text: str | None = None
    cost: dict = field(default_factory=dict)   # flops/bytes_accessed
    # {"axis": name, "sharded": [argnames], "replicated": [argnames]}
    # mirroring the launch site's in_shardings; None = no mesh at all
    partition: dict | None = None
    # [(argname, axis_index, why-it-is-embarrassingly-parallel)]
    batch_axes: list = field(default_factory=list)
    bucket_policy: str | None = None   # "pow2" | "quantized" | "linear"
    file: str | None = None
    line: int | None = None


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------

def iter_eqns(jaxpr) -> Iterator:
    """Every eqn in a (Closed)Jaxpr, recursing through call/control-
    flow sub-jaxprs (while bodies, cond branches, scans, pjit)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in getattr(inner, "eqns", ()):
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _sub_jaxprs(eqn) -> Iterator:
    for v in eqn.params.values():
        for j in _jaxprs_in(v):
            yield j


def _jaxprs_in(v) -> Iterator:
    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _jaxprs_in(x)


def eqn_provenance(eqn) -> tuple[str | None, int | None]:
    """file:line for an eqn via jaxpr source info (private jax API,
    best-effort: a jax upgrade degrades provenance, never the rule)."""
    try:
        from jax._src import source_info_util

        s = source_info_util.summarize(eqn.source_info)
        # "path/to/file.py:123 (fn_name)"
        loc = s.split(" ")[0]
        f, _, ln = loc.rpartition(":")
        return f or None, int(ln) if ln.isdigit() else None
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return None, None


def _aval_bytes(aval) -> int:
    try:
        import numpy as np

        n = 1
        for d in aval.shape:
            n *= int(d)
        return n * np.dtype(aval.dtype).itemsize
    except Exception:  # noqa: BLE001 — abstract avals only
        return 0


# ---------------------------------------------------------------------------
# R1 — host-sync
# ---------------------------------------------------------------------------

def rule_host_sync(trace: KernelTrace) -> list[Finding]:
    out: list[Finding] = []
    if trace.jaxpr is None:
        return out
    counts: dict[str, int] = {}
    for eqn in iter_eqns(trace.jaxpr):
        prim = eqn.primitive.name
        if prim not in HOST_SYNC_PRIMITIVES:
            continue
        n = counts.get(prim, 0)
        counts[prim] = n + 1
        f, ln = eqn_provenance(eqn)
        out.append(Finding(
            rule="R1", kernel=trace.name, site=f"{prim}:{n}",
            message=f"host-sync primitive `{prim}` inside the compiled "
                    f"kernel (bucket {trace.bucket}): every call is a "
                    "synchronous device->host->device round trip",
            file=f or trace.file, line=ln or trace.line,
            hint="compute it on device, or hoist the callback out of "
                 "the jitted program (pre/post-process on host)"))
    return out


# ---------------------------------------------------------------------------
# R2 — dtype widening (jaxpr side; host-feeder AST scan below)
# ---------------------------------------------------------------------------

def rule_dtype_widening(trace: KernelTrace) -> list[Finding]:
    out: list[Finding] = []
    if trace.jaxpr is None:
        return out
    seen: set[tuple[str, str]] = set()
    for eqn in iter_eqns(trace.jaxpr):
        prim = eqn.primitive.name
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in WIDE_DTYPES and (prim, dt) not in seen:
                seen.add((prim, dt))
                f, ln = eqn_provenance(eqn)
                out.append(Finding(
                    rule="R2", kernel=trace.name,
                    site=f"{dt}:{prim}",
                    message=f"64-bit intermediate ({dt} out of "
                            f"`{prim}`) in the kernel jaxpr: doubles "
                            "memory traffic vs the int32 house style",
                    file=f or trace.file, line=ln or trace.line,
                    cost_bytes=_aval_bytes(aval) // 2,
                    hint="keep device math in int32/float32 (or "
                         "narrower); check for x64 leaks and python "
                         "int promotion"))
        if prim == "convert_element_type":
            try:
                import numpy as np

                new = np.dtype(eqn.params.get("new_dtype"))
                old = np.dtype(eqn.invars[0].aval.dtype)
                if new.itemsize >= 8 and new.itemsize > old.itemsize \
                        and ("widen", str(new)) not in seen:
                    seen.add(("widen", str(new)))
                    f, ln = eqn_provenance(eqn)
                    out.append(Finding(
                        rule="R2", kernel=trace.name,
                        site=f"widen:{old}->{new}",
                        message=f"widening convert {old}->{new} "
                                "inside the kernel",
                        file=f or trace.file, line=ln or trace.line,
                        hint="narrow the target dtype"))
            except Exception:  # noqa: BLE001 — param shape drift
                pass
    return out


# host-feeder side: explicit 64-bit numpy dtypes in the modules that
# build kernel inputs. One finding per (function, dtype) so line churn
# inside a function doesn't move the baseline key.

_DTYPE_ATTRS = {"int64", "uint64", "float64"}


def scan_module_dtypes(module) -> list[Finding]:
    """AST scan of one host-feeder module for explicit 64-bit numpy
    dtypes (np.int64 / jnp.float64 / dtype="int64") inside function
    bodies — each one is host-side widening feeding the device."""
    try:
        src = inspect.getsource(module)
        fname = inspect.getsourcefile(module)
    except (OSError, TypeError):
        return []
    modname = module.__name__.rsplit(".", 1)[-1]
    return scan_source_dtypes(src, fname, modname)


def scan_source_dtypes(src: str, fname: str | None,
                       modname: str) -> list[Finding]:
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return []
    out: list[Finding] = []
    seen: set[tuple[str, str]] = set()

    def visit(node, func: str | None):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            func = node.name if func is None else f"{func}.{node.name}"
        for child in ast.iter_child_nodes(node):
            dt = _wide_dtype_of(child)
            if dt and func and (func, dt) not in seen:
                seen.add((func, dt))
                out.append(Finding(
                    rule="R2", kernel=f"module:{modname}",
                    site=f"{func}:{dt}",
                    message=f"explicit {dt} in host feeder "
                            f"{modname}.{func} (8-byte elements where "
                            "the kernels speak int32)",
                    file=fname, line=child.lineno,
                    hint="use int32/float32 unless the value range "
                         "genuinely needs 64 bits"))
            visit(child, func)

    visit(tree, None)
    return out


def _wide_dtype_of(node) -> str | None:
    if isinstance(node, ast.Attribute) and node.attr in _DTYPE_ATTRS \
            and isinstance(node.value, ast.Name) \
            and node.value.id in ("np", "jnp", "numpy"):
        return node.attr
    if isinstance(node, ast.keyword) and node.arg == "dtype" \
            and isinstance(node.value, ast.Constant) \
            and node.value.value in _DTYPE_ATTRS:
        return str(node.value.value)
    return None


# ---------------------------------------------------------------------------
# R3 — donation misses
# ---------------------------------------------------------------------------

def rule_donation(trace: KernelTrace) -> list[Finding]:
    out: list[Finding] = []
    for a in trace.args:
        if a.donated or a.nbytes < DONATION_MIN_BYTES:
            continue
        out.append(Finding(
            rule="R3", kernel=trace.name, site=a.name,
            message=f"arg `{a.name}` ({a.dtype}{list(a.shape)}, "
                    f"{a.nbytes / 1024:.0f} KiB) is not donated "
                    f"(bucket {trace.bucket}): the buffer stays live "
                    "across the launch instead of being reusable as "
                    "scratch/output",
            file=trace.file, line=trace.line,
            cost_bytes=a.nbytes,
            hint="add it to donate_argnums at the jit site (launch "
                 "sites re-create device arrays per call, so donation "
                 "is safe)"))
    return out


# ---------------------------------------------------------------------------
# R4 — sharding readiness
# ---------------------------------------------------------------------------

def rule_sharding(trace: KernelTrace) -> list[Finding]:
    out: list[Finding] = []
    part = trace.partition
    args = {a.name: a for a in trace.args}
    if part:
        for name in part.get("replicated", ()):
            a = args.get(name)
            if a is None or a.nbytes < REPLICATED_MIN_BYTES:
                continue
            out.append(Finding(
                rule="R4", kernel=trace.name,
                site=f"replicated:{name}",
                message=f"arg `{name}` ({a.nbytes / 1024:.0f} KiB) is "
                        "fully replicated across the "
                        f"'{part.get('axis')}' mesh axis (bucket "
                        f"{trace.bucket}): H2D cost and HBM footprint "
                        "scale with device count, work does not",
                file=trace.file, line=trace.line,
                cost_bytes=a.nbytes,
                hint="give it a partition rule (shard the segment/"
                     "row dimension) or slice it per shard"))
    for name, axis, why in trace.batch_axes:
        if part and name in (part.get("sharded") or ()):
            continue
        a = args.get(name)
        out.append(Finding(
            rule="R4", kernel=trace.name,
            site=f"unsharded-axis:{name}.{axis}",
            message=f"batch axis {axis} of `{name}` is embarrassingly "
                    f"parallel ({why}) but has no partition rule "
                    f"(bucket {trace.bucket}): the mesh adds devices "
                    "without adding throughput",
            file=trace.file, line=trace.line,
            cost_bytes=a.nbytes if a else None,
            hint="lay this axis out over the mesh (shard_map/pjit "
                 "with a PartitionSpec on it; SNIPPETS.md [1]-[3])"))
    if trace.hlo_text:
        low = trace.hlo_text.lower()
        for coll in HLO_COLLECTIVES:
            if coll in low:
                out.append(Finding(
                    rule="R4", kernel=trace.name,
                    site=f"collective:{coll}",
                    message=f"lowered HLO contains `{coll}` (bucket "
                            f"{trace.bucket}): an op inside the "
                            "partitioned program forces cross-device "
                            "gathering",
                    file=trace.file, line=trace.line,
                    hint="check the op's partition spec; reformulate "
                         "to keep the batch axis local"))
    return out


# ---------------------------------------------------------------------------
# R5 — recompile risk
# ---------------------------------------------------------------------------

def rule_recompile(trace: KernelTrace) -> list[Finding]:
    out: list[Finding] = []
    # jax keeps closure captures on the ClosedJaxpr as typed arrays
    # that carry shape and dtype but no nbytes
    sizes = [_aval_bytes(c)
             for c in (getattr(trace.jaxpr, "consts", ()) or ())
             if hasattr(c, "shape") and hasattr(c, "dtype")]
    big = [b for b in sizes if b >= LARGE_CONST_BYTES]
    small = [b for b in sizes if b < LARGE_CONST_BYTES]
    if small:
        # every captured value bakes into the traced program: a
        # varying capture (config, rng state, a python-built table)
        # is one retrace+recompile per distinct value
        out.append(Finding(
            rule="R5", kernel=trace.name, site="captured-consts",
            message=f"{len(small)} closure-captured array const(s) "
                    "in the jaxpr: each distinct captured value is "
                    "its own traced program (one recompile per "
                    "value if the capture varies)",
            file=trace.file, line=trace.line, severity="info",
            hint="pass varying values as arguments; keep closure "
                 "captures to true constants"))
    if big:
        total = sum(big)
        out.append(Finding(
            rule="R5", kernel=trace.name, site="large-consts",
            message=f"{len(big)} closure-captured array const(s) "
                    f"totalling {total / 1024:.0f} KiB bloat every "
                    "compiled executable of this bucket",
            file=trace.file, line=trace.line, cost_bytes=total,
            hint="pass large tables as arguments so the executable "
                 "is shape-generic"))
    if trace.bucket_policy == "linear":
        out.append(Finding(
            rule="R5", kernel=trace.name, site="bucket-policy",
            message="shape buckets grow linearly (not pow2/"
                    "quantized): bucket cardinality — and compile "
                    "count — is unbounded in input size",
            file=trace.file, line=trace.line,
            hint="quantize the padded shape (next_pow2 or coarse "
                 "fixed steps) so the compile cache saturates"))
    return out


def runtime_bucket_findings(buckets: dict[str, set],
                            max_buckets: int = MAX_RUNTIME_BUCKETS
                            ) -> list[Finding]:
    """R5's runtime cross-check over profiler.shape_buckets(): a
    kernel that compiled more than max_buckets distinct shapes this
    process is leaking shapes through its bucketing policy."""
    out = []
    for kernel, bs in sorted(buckets.items()):
        if len(bs) > max_buckets:
            out.append(Finding(
                rule="R5", kernel=kernel, site="bucket-cardinality",
                message=f"{len(bs)} distinct compiled shape buckets "
                        f"this process (> {max_buckets}): each one "
                        "paid a full XLA compile",
                hint="coarsen the bucket quantization "
                     "(profiler.<k>.bucket_cardinality tracks this "
                     "per run)"))
    return out


# ---------------------------------------------------------------------------
# R6 — while-loop carry bloat
# ---------------------------------------------------------------------------

def rule_carry(trace: KernelTrace) -> list[Finding]:
    out: list[Finding] = []
    if trace.jaxpr is None:
        return out
    n = 0
    for eqn in iter_eqns(trace.jaxpr):
        if eqn.primitive.name != "while":
            continue
        body = eqn.params.get("body_jaxpr")
        avals = list(getattr(body, "out_avals", ()) or ())
        sizes = sorted(((_aval_bytes(a), a) for a in avals),
                       key=lambda t: -t[0])
        total = sum(s for s, _ in sizes)
        site = f"while:{n}"
        n += 1
        if total < CARRY_WARN_BYTES:
            continue
        top = ", ".join(
            f"{str(getattr(a, 'dtype', '?'))}{list(a.shape)}"
            f"={s // 1024}KiB" for s, a in sizes[:3])
        f, ln = eqn_provenance(eqn)
        out.append(Finding(
            rule="R6", kernel=trace.name, site=site,
            message=f"while-loop carry is {total / 1024:.0f} KiB "
                    f"(bucket {trace.bucket}; largest: {top}): every "
                    "carry byte is serialized through every BFS "
                    "level",
            file=f or trace.file, line=ln or trace.line,
            cost_bytes=total,
            hint="move per-level accumulators (telemetry series, "
                 "debug state) out of the carry, or narrow/pack the "
                 "frontier encoding (int8/int16 state packing)"))
    return out


# ---------------------------------------------------------------------------
# Rule suite + baseline ratchet
# ---------------------------------------------------------------------------

TRACE_RULES = (rule_host_sync, rule_dtype_widening, rule_donation,
               rule_sharding, rule_recompile, rule_carry)


def run_rules(trace: KernelTrace) -> list[Finding]:
    """The full R1-R6 suite over one kernel trace."""
    out: list[Finding] = []
    for rule in TRACE_RULES:
        out.extend(rule(trace))
    return out


BASELINE_VERSION = 1


def load_baseline(path) -> dict:
    """The committed baseline document ({"version", "findings"});
    an empty skeleton when the file doesn't exist."""
    p = Path(path)
    if not p.exists():
        return {"version": BASELINE_VERSION, "findings": []}
    with open(p) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or \
            not isinstance(doc.get("findings"), list):
        raise ValueError(f"{path}: not a lint baseline document")
    return doc


def baseline_doc(findings: list[Finding]) -> dict:
    """A baseline document pinning `findings` (sorted by key so the
    committed file diffs cleanly)."""
    return {
        "version": BASELINE_VERSION,
        "findings": sorted(
            ({"key": f.key, "rule": f.rule, "kernel": f.kernel,
              "site": f.site, "message": f.message}
             for f in findings), key=lambda d: d["key"]),
    }


def write_baseline(path, findings: list[Finding]) -> dict:
    doc = baseline_doc(findings)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return doc


def ratchet(findings: list[Finding], baseline: dict) -> dict:
    """The baseline comparison: {'new': [Finding], 'baselined':
    [Finding], 'stale': [keys]}. NEW findings fail the gate; STALE
    baseline entries (fixed findings) are prune candidates —
    `--update` rewrites the file without them, so the ratchet only
    ever tightens."""
    known = {e["key"] for e in baseline.get("findings", ())}
    have = {f.key for f in findings}
    return {
        "new": [f for f in findings if f.key not in known],
        "baselined": [f for f in findings if f.key in known],
        "stale": sorted(known - have),
    }
