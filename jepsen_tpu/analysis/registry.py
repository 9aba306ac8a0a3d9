"""The kernel registry: every compiled entry point, abstractly traced.

Each entry names one compiled entry point of the checker pipeline
(wgl `check_batch`/`check_batch_reach`/`check_segmented`, the
mesh-sharded ensemble path, the SCC coloring kernel — the device
engine under both elle check functions — and the host-side
encode/PackedBatch feeders) and knows how to trace it at a shape
bucket: ShapeDtypeStructs through the REAL jit factories
(`wgl._jitted_kernel`, `ensemble._jitted_sharded`, `scc._jitted_scc`),
so donation flags, static config and partition layout are read off
the artifacts that actually launch — no execution, no devices beyond
one, CPU-safe (tier-1 runs this).

Default buckets are fixed and deterministic (the committed baseline
must not depend on what this process happened to compile); pass
runtime buckets from profiler.shape_buckets() to additionally trace
the shapes a live run actually used.
"""

from __future__ import annotations

import functools
import inspect
from dataclasses import dataclass, field
from typing import Callable

from ..tpu.lint import ArgSpec, KernelTrace

# Arg names come from the kernel modules themselves
# (ensemble.SHARD_ARGS, scc.SCC_ARGS — imported lazily in the trace
# functions): one definition next to each signature, so the registry
# can't hold a stale parallel copy of the layout it prices.


def _provenance(fn) -> tuple[str | None, int | None]:
    try:
        f = inspect.unwrap(fn)
        return (inspect.getsourcefile(f),
                inspect.getsourcelines(f)[1])
    except (OSError, TypeError):
        return None, None


def _argspecs(names, sds_args, donated) -> list[ArgSpec]:
    import numpy as np

    out = []
    for name, a, d in zip(names, sds_args, donated):
        n = 1
        for dim in a.shape:
            n *= int(dim)
        out.append(ArgSpec(name=name, shape=tuple(a.shape),
                           dtype=str(a.dtype),
                           nbytes=n * np.dtype(a.dtype).itemsize,
                           donated=bool(d)))
    return out


def _donated_flags(staged, n_args: int) -> list[bool]:
    """Donation flags off a jax.stages.Traced/Lowered args_info
    pytree (positional)."""
    try:
        import jax.tree_util as jtu

        flat, _ = jtu.tree_flatten(
            staged.args_info, is_leaf=lambda x: hasattr(x, "donated"))
        flags = [bool(getattr(a, "donated", False)) for a in flat]
        if len(flags) == n_args:
            return flags
    except Exception:  # noqa: BLE001 — jax API drift degrades to False
        pass
    return [False] * n_args


def _cost(lowered) -> dict:
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return {k: float(ca[k]) for k in ("flops", "bytes accessed")
                if isinstance(ca, dict) and ca.get(k) is not None}
    except Exception:  # noqa: BLE001 — cost is best-effort
        return {}


@dataclass
class Entry:
    """One registry entry. trace(bucket, full=False) -> KernelTrace;
    buckets are dicts whose 'label' keys the finding sites (stable
    across PRs). full=False (the canonical/baseline mode) stops at
    jax tracing — jaxpr + donation flags, ~100ms/kernel; full=True
    additionally LOWERS the program for HLO text (R4's collective
    scan) and XLA cost analysis."""

    name: str
    trace: Callable[..., KernelTrace]
    buckets: list = field(default_factory=list)
    doc: str = ""


# ---------------------------------------------------------------------------
# wgl batched search (check_batch / check_batch_reach / check_segmented)
# ---------------------------------------------------------------------------

def _wgl_sds(b: dict):
    import jax
    import numpy as np

    K, M, S, rows = b["K"], b["M"], b["S"], b["rows"]
    sds = jax.ShapeDtypeStruct
    return (sds((K, M), np.int32), sds((K, M), np.int32),
            sds((K, M, S), np.int32), sds((K,), np.int32),
            sds((K, M + 1), np.int32), sds((rows,), np.int32),
            sds((rows,), np.int32), sds((rows,), np.int32))


def _mesh1():
    """A 1-device mesh: the sharded jit factories are the REAL launch
    artifacts on any mesh size, and tracing them on one device keeps
    the registry deterministic and CPU-safe (tier-1 runs this)."""
    import numpy as np

    import jax

    from ..tpu import spmd

    return jax.sharding.Mesh(np.array(jax.devices()[:1]), (spmd.AXIS,))


def _staged(traced, full: bool):
    """(jaxpr, donated_source, hlo_text, cost) off a Traced; lowering
    only in full mode."""
    if not full:
        return traced.jaxpr, traced, None, {}
    lowered = traced.lower()
    return traced.jaxpr, lowered, lowered.as_text(), _cost(lowered)


def _wgl_trace(b: dict, kernel_name: str,
               full: bool = False) -> KernelTrace:
    """Every wgl entry point launches through the SPMD program
    (ensemble._jitted_sharded via wgl._launch) whenever the process
    has >1 device, so THAT factory is what the registry traces: the
    partition layout, donation flags and jaxpr are read off the
    artifact the mesh actually runs. The partition metadata comes
    from the same rule table the launch sites use (tpu/spmd.py) —
    graftlint R4 prices the real layout, not a parallel description."""
    from ..tpu import ensemble, spmd, wgl

    fn = ensemble._jitted_sharded(_mesh1(), b["W"], b["F"],
                                  b["M"] + 4, b.get("reach", False),
                                  b.get("crash_free", False))
    args = _wgl_sds(b)
    traced = fn.trace(*args)
    jaxpr, staged, hlo, cost = _staged(traced, full)
    f, ln = _provenance(ensemble.check_batch_sharded
                        if kernel_name == "wgl-sharded"
                        else wgl._kernel)
    return KernelTrace(
        name=kernel_name, bucket=b["label"], jaxpr=jaxpr,
        args=_argspecs(ensemble.SHARD_ARGS, args,
                       _donated_flags(staged, len(args))),
        hlo_text=hlo, cost=cost,
        partition=spmd.describe_partition(spmd.WGL_RULES,
                                          ensemble.SHARD_ARGS),
        batch_axes=[("row_seg", 0,
                     "independent search rows: one history / "
                     "(segment, start-state) pair per row")],
        bucket_policy="pow2", file=f, line=ln)


# ---------------------------------------------------------------------------
# mesh-sharded ensemble path (check_batch_sharded)
# ---------------------------------------------------------------------------

def _sharded_trace(b: dict, full: bool = False) -> KernelTrace:
    return _wgl_trace(b, kernel_name="wgl-sharded", full=full)


def _single_trace(b: dict, full: bool = False) -> KernelTrace:
    """The plain single-device jit path (wgl._jitted_kernel) — what
    wgl._launch runs on a 1-device process, under JEPSEN_TPU_SPMD=0
    (the documented differential reference), and at the bottom of the
    degradation ladder. The SPMD program owns the production batch
    axis (R4 prices it on the entries above), so this trace declares
    no batch axes; it exists to keep R1/R2/R3/R6 coverage of the
    fallback's jaxpr — a donation or dtype regression in a
    plain-path-only branch must not hide behind the sharded trace."""
    from ..tpu import ensemble, wgl

    kw = dict(W=b["W"], F=b["F"], max_iters=b["M"] + 4,
              reach=b.get("reach", False),
              crash_free=b.get("crash_free", False))
    args = _wgl_sds(b)[:7]  # no inv_perm: the plain kernel signature
    traced = wgl._jitted_kernel().trace(*args, **kw)
    jaxpr, staged, hlo, cost = _staged(traced, full)
    f, ln = _provenance(wgl._kernel)
    return KernelTrace(
        name="wgl-single", bucket=b["label"], jaxpr=jaxpr,
        args=_argspecs(ensemble.SHARD_ARGS[:7], args,
                       _donated_flags(staged, len(args))),
        hlo_text=hlo, cost=cost,
        partition=None,
        batch_axes=[],
        bucket_policy="pow2", file=f, line=ln)


# ---------------------------------------------------------------------------
# SCC coloring kernel (scc_device — the device engine under both
# elle_device check functions)
# ---------------------------------------------------------------------------

def _scc_trace(b: dict, full: bool = False,
               kernel_name: str = "scc") -> KernelTrace:
    import jax
    import numpy as np

    from ..tpu import scc, spmd

    n_pad, e_pad = b["n_pad"], b["e_pad"]
    single = kernel_name == "scc-single"
    if single:
        # the plain single-device compile — what scc_device runs on a
        # 1-device process and under JEPSEN_TPU_SPMD=0 (same rationale
        # as wgl-single: the fallback's donation/dtype/carry must not
        # hide behind the sharded trace). No batch axes declared: the
        # sharded entry owns the R4 story.
        fn = scc._jitted_scc(n_pad, e_pad, scc.SWEEP_CAP,
                             scc.ROUND_CAP)
    else:
        fn = scc._jitted_scc_sharded(_mesh1(), n_pad, e_pad,
                                     scc.SWEEP_CAP, scc.ROUND_CAP)
    args = tuple(jax.ShapeDtypeStruct(s, d)
                 for s, d in scc.scc_arg_shapes(n_pad, e_pad))
    traced = fn.trace(*args)
    jaxpr, staged, hlo, cost = _staged(traced, full)
    f, ln = _provenance(scc.scc_device)
    return KernelTrace(
        name=kernel_name, bucket=b["label"], jaxpr=jaxpr,
        args=_argspecs(scc.SCC_ARGS, args,
                       _donated_flags(staged, len(args))),
        hlo_text=hlo, cost=cost,
        partition=None if single else
        spmd.describe_partition(spmd.SCC_RULES, scc.SCC_ARGS),
        batch_axes=[] if single else
        [("fwd_send", 0,
          "edge list: each shard pulls each node's max over its "
          "runs in its own block, per-edge data-parallel")],
        # edge buckets step linearly in 128Ki chunks above 2^17
        # (scc._edge_pad) — R5 prices that policy
        bucket_policy="linear", file=f, line=ln)


# ---------------------------------------------------------------------------
# Default shape buckets (deterministic; mirror the profiler's real
# buckets from the BASELINE configs: ensemble batches, segmented long
# histories, elle SCC graphs)
# ---------------------------------------------------------------------------

WGL_BUCKETS = [
    # check_batch over a 64-history bucket (the ensemble chunk shape)
    {"label": "B64xM512xS8", "K": 65, "M": 512, "S": 8, "rows": 64,
     "W": 32, "F": 64, "reach": False, "crash_free": False},
]
WGL_REACH_BUCKETS = [
    {"label": "B64xM512xS8", "K": 65, "M": 512, "S": 8, "rows": 64,
     "W": 32, "F": 32, "reach": True, "crash_free": False},
]
WGL_SEG_BUCKETS = [
    # check_segmented: K segments x S start states of one long history
    {"label": "K8xM8192xS8", "K": 9, "M": 8192, "S": 8, "rows": 128,
     "W": 24, "F": 48, "reach": True, "crash_free": False},
]
SHARDED_BUCKETS = [
    # the 1024-history ensemble (BASELINE config 5)
    {"label": "B1024xM512xS8", "K": 1025, "M": 512, "S": 8,
     "rows": 1024, "W": 32, "F": 64, "reach": False},
]
SLICES_BUCKETS = [
    # the fleet's cross-run slice launch (jepsen_tpu.fleet.scheduler
    # via wgl.check_slices): ~32 tenant stream segments x a handful of
    # live start states each — short segments, small state spaces,
    # many rows (doc/fleet.md)
    {"label": "B32xM512xS8", "K": 33, "M": 512, "S": 8, "rows": 128,
     "W": 24, "F": 48, "reach": True, "crash_free": False},
]
SCC_BUCKETS = [
    # elle dependency graphs at the 100k-txn scale (BASELINE config 3)
    {"label": "N131072xE262144", "n_pad": 131072, "e_pad": 262144},
]


def entries() -> list[Entry]:
    return [
        Entry("wgl", functools.partial(_wgl_trace,
                                       kernel_name="wgl"),
              WGL_BUCKETS, "check_batch batched frontier search"),
        Entry("wgl-reach",
              functools.partial(_wgl_trace, kernel_name="wgl-reach"),
              WGL_REACH_BUCKETS,
              "check_batch_reach exhaustive reachability"),
        Entry("wgl-segmented",
              functools.partial(_wgl_trace,
                                kernel_name="wgl-segmented"),
              WGL_SEG_BUCKETS,
              "check_segmented per-segment reach rows"),
        Entry("wgl-sharded", _sharded_trace, SHARDED_BUCKETS,
              "check_batch_sharded mesh ensemble path"),
        Entry("wgl-single", _single_trace, WGL_BUCKETS,
              "single-device fallback jit (1-device processes, "
              "JEPSEN_TPU_SPMD=0, ladder floor)"),
        Entry("wgl-slices",
              functools.partial(_wgl_trace,
                                kernel_name="wgl-slices"),
              SLICES_BUCKETS,
              "check_slices fleet cross-tenant reach rows"),
        Entry("scc", _scc_trace, SCC_BUCKETS,
              "Orzan coloring SCC (elle_device cycle engine)"),
        Entry("scc-single",
              functools.partial(_scc_trace,
                                kernel_name="scc-single"),
              SCC_BUCKETS,
              "single-device SCC compile (1-device processes, "
              "JEPSEN_TPU_SPMD=0)"),
    ]


def host_feeder_modules() -> list:
    """Modules whose host-side array code feeds the kernels in int32
    house style — the R2 dtype audit targets. elle/elle_device are
    deliberately exempt: their packed (key, value) edge codes need 64
    bits by design, and scc.py narrows them to int32 at the device
    boundary."""
    from ..tpu import encode, ensemble, scc, wgl

    return [encode, wgl, scc, ensemble]


def runtime_wgl_buckets(raw_buckets) -> list[dict]:
    """Translates wgl._compiled_buckets tuples — via
    profiler.shape_buckets()['wgl'] — back into traceable bucket
    dicts. Unparseable tuples (mesh-sharded entries carry a live Mesh)
    are skipped: runtime buckets only ever ADD traces."""
    out = []
    for t in sorted(raw_buckets, key=repr):
        try:
            (K, M), S, rows, W, F, max_iters, reach, has_crashed = t
        except (TypeError, ValueError):
            continue
        if not all(isinstance(x, int)
                   for x in (K, M, S, rows, W, F, max_iters)):
            continue
        out.append({"label": f"rt-B{rows}xM{M}xS{S}"
                             + ("r" if reach else ""),
                    "K": K, "M": M, "S": S, "rows": rows, "W": W,
                    "F": F, "reach": bool(reach),
                    "crash_free": not has_crashed})
    return out
