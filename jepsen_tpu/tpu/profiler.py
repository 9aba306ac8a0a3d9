"""Device-performance profiling for compiled-kernel launches.

telemetry.py answers "where did the run spend its time?"; this module
answers "what did the device DO with it?". Every compiled-kernel launch
site (wgl batched search, the mesh-sharded ensemble path, the SCC
coloring kernel, the elle device engines, plus the host-side encode and
pack stages that feed them) reports a per-launch record:

  - the wall vs device split: host encode / H2D transfer / dispatch /
    device compute / D2H readback, each phase timed separately;
  - compile-cache accounting per shape bucket (hooked into the
    existing wgl._compiled_buckets claim), so the profile shows hit
    rates, not just compile totals;
  - for mesh-sharded launches: per-device work attribution (entries of
    search work landing on each chip) and a load-balance figure — the
    data that explains a flat device-count sweep.

Records flow through the existing observability fabric: each finished
launch mirrors into telemetry as a `kernel:<name>` span (so it lands in
telemetry.jsonl and the Perfetto export's device track) and as
`profiler.<kernel>.*` counters/gauges (so metrics.json carries the
aggregate the `profile` CLI and web section render). The recorder is
always on and adds two dict updates per launch. A kernel's static
FLOPs/bytes are graftlint's (analysis/registry.py); peak device memory
is Device.memory_stats()'s.

parallel_efficiency() below is the scaling metric that
`chip_smoke.py --chips 4` reports for the sharded paths.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable

from .. import telemetry, util

logger = logging.getLogger(__name__)

# Launch-phase keys, in pipeline order. Every *_ns field of a record
# that isn't one of these is still additive host time (compile_ns).
PHASES = ("encode_ns", "h2d_ns", "dispatch_ns", "compute_ns", "d2h_ns")

# Per-launch records are mirrored into telemetry individually only up
# to this many launches per kernel per run; past it, only aggregates
# accumulate (a 1024-history ensemble must not write 1024 span lines).
# The cap is configurable (JEPSEN_TPU_PROFILE_MAX_SPANS) and NEVER
# silent: every launch past it counts `profiler.<k>.spans_dropped` in
# metrics.json, so a truncated telemetry mirror is visible instead of
# reading as "that's all the launches there were".
MAX_MIRRORED_LAUNCHES = 64


def max_mirrored_launches() -> int:
    try:
        return int(os.environ.get("JEPSEN_TPU_PROFILE_MAX_SPANS",
                                  MAX_MIRRORED_LAUNCHES))
    except ValueError:
        return MAX_MIRRORED_LAUNCHES


class Profiler:
    """Per-launch device-profile recorder. Thread-safe; one global
    instance (get()) serves the process, tests may make their own.
    `enabled=False` makes it a no-op recorder: records still open and
    park (call sites mutate them unconditionally) but nothing is
    aggregated or mirrored to telemetry."""

    # concurrency-lint contract (jepsen_tpu.analysis.concurrency,
    # doc/static-analysis.md): these attrs are written under _lock
    # only (or in *_locked methods whose callers hold it)
    _guarded_by_lock = {"_lock": ("_records", "_pending",
                                  "_seen_buckets", "cache_stats")}

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._records: list[dict] = []
        self._pending: dict[int, dict] = {}   # id(device out) -> record
        self._seen_buckets: dict[str, set] = {}
        self.cache_stats: dict[str, dict] = {}

    # -- launch records ----------------------------------------------------

    def begin(self, kernel: str, bucket=None, **attrs) -> dict:
        """Opens a launch record. `kernel` is a dot-free site name
        ('wgl', 'scc', ...); `bucket` the compile-shape key."""
        rec: dict = {"kernel": kernel.replace(".", "-"),
                     "t0": util.relative_time_nanos(),
                     # straggler guard: a telemetry.reset() (next run
                     # starting) before this record finishes means its
                     # clock origin is stale — _finish_locked drops it
                     "_epoch": telemetry.get().epoch}
        if bucket is not None:
            rec["bucket"] = repr(bucket)
        for k, v in attrs.items():
            if v is not None:
                rec[k] = v
        return rec

    @contextmanager
    def phase(self, rec: dict | None, name: str):
        """Times one pipeline phase (see PHASES) into the record."""
        if rec is None:
            yield
            return
        t0 = time.monotonic_ns()
        try:
            yield
        finally:
            rec[name] = rec.get(name, 0) + (time.monotonic_ns() - t0)

    def cache_event(self, kernel: str, fresh: bool) -> None:
        """One compile-cache lookup: miss (fresh bucket, compiling) or
        hit (bucket already compiled this process)."""
        if not self.enabled:
            return
        kernel = kernel.replace(".", "-")
        with self._lock:
            st = self.cache_stats.setdefault(kernel,
                                             {"hits": 0, "misses": 0})
            st["misses" if fresh else "hits"] += 1
        telemetry.count(f"profiler.{kernel}.compile."
                        + ("miss" if fresh else "hit"))

    def bucket_fresh(self, site: str, bucket) -> bool:
        """First-sighting test for launch sites without their own
        compiled-bucket set (scc); counts the cache event too, and
        gauges the site's distinct-bucket cardinality (set size, NOT
        the miss counter: a failed first launch is unclaimed and
        retried, and its second miss must not inflate the gauge) —
        graftlint R5's runtime cross-check."""
        with self._lock:
            seen = self._seen_buckets.setdefault(site, set())
            fresh = bucket not in seen
            if fresh:
                seen.add(bucket)
            n = len(seen)
        self.cache_event(site, fresh)
        if fresh:
            telemetry.gauge(f"profiler.{site}.bucket_cardinality", n)
        return fresh

    def bucket_unclaim(self, site: str, bucket) -> None:
        """Un-claims a bucket whose first launch failed (the analog of
        wgl._timed_launch discarding its _compiled_buckets claim): the
        next attempt really recompiles and must record a miss + fresh
        compile_ns, not a phantom cache hit."""
        with self._lock:
            self._seen_buckets.get(site, set()).discard(bucket)

    def attach(self, out, rec: dict) -> Any:
        """Parks an open record until the launch's output is drained
        (the async-dispatch handoff: _launch returns, _drain blocks).
        Keyed by the output object's id — the caller holds the output
        alive until drain, so the id can't be recycled underneath."""
        if rec is None:
            return out
        with self._lock:
            if len(self._pending) > 256:
                # exception paths may abandon records; cap the parking
                # lot, finalizing EVERY stray so all of them still
                # aggregate (an in-flight launch loses its parked
                # record to the sweep — its _drain finds None — but
                # its dispatch-side phases are preserved here)
                for stray in list(self._pending.values()):
                    self._finish_locked(stray)
                self._pending.clear()
            self._pending[id(out)] = rec
        return out

    def take(self, out) -> dict | None:
        with self._lock:
            return self._pending.pop(id(out), None)

    def finish(self, rec: dict | None) -> dict | None:
        """Closes a record: stamps t1, mirrors it into telemetry (span
        + per-kernel aggregate counters)."""
        if rec is None:
            return None
        with self._lock:
            self._finish_locked(rec)
        return rec

    def _finish_locked(self, rec: dict) -> None:
        if "t1" in rec:
            return
        rec["t1"] = util.relative_time_nanos()
        epoch = rec.pop("_epoch", None)
        tel = telemetry.get()
        if not self.enabled or (epoch is not None
                                and epoch != tel.epoch):
            # disabled recorder, or a straggler finishing after the
            # next run began: its t0 was measured against the previous
            # run's clock origin — dropping beats misfiling
            return
        self._records.append(rec)
        k = rec["kernel"]
        tel.count(f"profiler.{k}.launches")
        wall = max(rec["t1"] - rec["t0"], 0)
        tel.count(f"profiler.{k}.wall_ns", wall)
        for ph in PHASES:
            if rec.get(ph):
                tel.count(f"profiler.{k}.{ph}", int(rec[ph]))
        if rec.get("compile_ns"):
            tel.count(f"profiler.{k}.compile_ns", int(rec["compile_ns"]))
        if rec.get("iterations"):
            tel.count(f"profiler.{k}.iterations", int(rec["iterations"]))
        if rec.get("rows"):
            tel.count(f"profiler.{k}.rows", int(rec["rows"]))
        # search-explorer aggregates (wgl _drain attaches the series)
        if rec.get("states_explored"):
            tel.count(f"profiler.{k}.states",
                      int(rec["states_explored"]))
        if rec.get("dedup_hits"):
            tel.count(f"profiler.{k}.dedup_hits",
                      int(rec["dedup_hits"]))
        if rec.get("frontier_peak"):
            tel.gauge_max(f"profiler.{k}.frontier_peak",
                          int(rec["frontier_peak"]))
        if rec.get("devices"):
            tel.gauge_max(f"profiler.{k}.devices", int(rec["devices"]))
        if rec.get("balance") is not None:
            tel.gauge(f"profiler.{k}.balance", rec["balance"])
        n_k = sum(1 for r in self._records if r["kernel"] == k)
        if n_k <= max_mirrored_launches():
            attrs = {kk: v for kk, v in rec.items()
                     if kk not in ("kernel", "t0", "t1")
                     and v is not None}
            tel.record_span(f"kernel:{k}", rec["t0"], rec["t1"], attrs,
                            epoch=epoch)
        else:
            # no silent caps: truncation of the telemetry mirror is
            # itself a metric (aggregates above still saw the launch)
            tel.count(f"profiler.{k}.spans_dropped")

    # -- simple sites ------------------------------------------------------

    def record_host(self, kernel: str, ns: int, **attrs) -> None:
        """Aggregate-only accounting for cheap host stages (encode,
        pack) that run thousands of times per analysis: counters only,
        no per-call record."""
        if not self.enabled:
            return
        k = kernel.replace(".", "-")
        tel = telemetry.get()
        tel.count(f"profiler.{k}.launches")
        tel.count(f"profiler.{k}.wall_ns", int(ns))
        tel.count(f"profiler.{k}.encode_ns", int(ns))
        for name, v in attrs.items():
            if isinstance(v, (int, float)) and v:
                tel.count(f"profiler.{k}.{name}", int(v))

    # -- views / lifecycle -------------------------------------------------

    def records(self) -> list[dict]:
        with self._lock:
            return list(self._records)

    def reset(self) -> None:
        """Clears per-run state. The bucket seen-sets persist: they
        mirror the process-level XLA compile cache, which a new run
        still hits."""
        with self._lock:
            self._records = []
            self._pending = {}
            self.cache_stats = {}

    # -- shape buckets -----------------------------------------------------

    def shape_buckets(self) -> dict[str, set]:
        """Every compiled shape bucket this process has seen, per
        launch site: this recorder's own seen-sets (scc et al) merged
        with the wgl kernel's _compiled_buckets claim set (which the
        single-device and mesh-sharded launch paths share). The
        lint's trace-shape source (graftlint R5 cross-checks the
        cardinality; the registry re-traces the real wgl shapes)."""
        with self._lock:
            out = {site: set(s)
                   for site, s in self._seen_buckets.items()}
        try:
            from . import wgl as _wgl  # lazy: wgl imports this module

            with _wgl._buckets_lock:  # snapshot vs concurrent claims
                claimed = set(_wgl._compiled_buckets)
            out.setdefault("wgl", set()).update(claimed)
        except Exception:  # noqa: BLE001 — accessor is best-effort
            logger.debug("wgl bucket set unavailable", exc_info=True)
        return out


_global = Profiler()


def get() -> Profiler:
    return _global


def reset() -> None:
    _global.reset()


def shape_buckets() -> dict[str, set]:
    """Module-level façade over Profiler.shape_buckets()."""
    return _global.shape_buckets()


# ---------------------------------------------------------------------------
# Scaling attribution
# ---------------------------------------------------------------------------

def parallel_efficiency(times: dict[int, float]) -> dict[int, float]:
    """Per-mesh-size parallel efficiency from a {n_devices: seconds}
    sweep: eff(N) = T(1) / (T(N) * N). 1.0 = perfect linear scaling;
    a flat sweep shows ~1/N."""
    t1 = times.get(1)
    if not t1:
        return {}
    return {int(n): round(t1 / (t * n), 4)
            for n, t in sorted(times.items()) if n >= 1 and t > 0}


# Mesh sizes at least this big with efficiency below this floor get a
# loud warning (chip_smoke.py --chips 4 checks it).
EFFICIENCY_WARN_N = 4
EFFICIENCY_WARN_FLOOR = 0.5


def check_efficiency(eff: dict[int, float],
                     log: Callable[[str], None] | None = None) -> list:
    """Returns [(n, eff)] for every mesh size >= EFFICIENCY_WARN_N
    scaling under the floor, logging each (the flat-sweep tripwire)."""
    bad = [(n, e) for n, e in sorted(eff.items())
           if n >= EFFICIENCY_WARN_N and e < EFFICIENCY_WARN_FLOOR]
    emit = log or logger.warning
    for n, e in bad:
        emit(f"parallel efficiency at {n} devices is {e:.2f} "
             f"(< {EFFICIENCY_WARN_FLOOR}): the mesh adds devices "
             "without adding throughput")
    return bad


def work_balance(work) -> float | None:
    """Load-balance figure for a sharded launch's per-device work
    attribution: mean/max — 1.0 is a perfectly even mesh, and with
    zero replicated bytes (graftlint R4) an uneven balance is what's
    left to explain a flat device sweep. None when no work landed."""
    import numpy as np

    work = [int(w) for w in work]
    if not work or max(work) == 0:
        return None
    return round(float(np.mean(work)) / max(work), 4)
