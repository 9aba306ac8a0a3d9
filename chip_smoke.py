#!/usr/bin/env python
"""Bring-up smoke run of the history checkers on the TPU.

    python chip_smoke.py             # one chip, every phase
    python chip_smoke.py --chips 4   # the sharded paths, 4 chips vs 1

Drives the analysis plane once through the entry points users call, at
the BASELINE configs' widths, with every history generated from a fixed
seed, all in this one process (a chip belongs to one process):

  linearizability  config 2: a ~1M-event CAS-register history through
                   checker.linearizable (the core.analyze path), valid
                   and with one anomaly seeded at 85%; both certificates
                   must validate.
  ensemble         config 5: 1024 x 400-op histories with 15% crashed
                   ops through wgl.analysis_batch_streamed; a seeded
                   subset is re-decided by the host search.
  elle             config 3: 100k list-append txns through
                   elle.check_list_append on the device engine and the
                   host engine, valid and with a seeded G1c cycle.
  fleet            an in-process FleetServer with 8 tenants streaming
                   3000-op histories in 512-op chunks; each verdict must
                   equal a solo wgl.analysis.

The library degrades to exact host searches when the device fails, so
correct verdicts alone do not show that the chip did the work. Every
phase therefore also reads the telemetry and profiler counters and
fails on any degradation rung, host fallback, quarantine or missing
kernel launch.

With --chips 4 only the linearizability, ensemble and elle checks run,
each on the default SPMD routing over 4 chips and again with
JEPSEN_TPU_SPMD_DEVICES=1 (re-read per call); verdicts and certificates
must be equal, and per-device peak memory must show the data spread
over the chips.

Per-phase walls, first-call walls (compile included), compile-cache
hits and peak device bytes print as diagnostics, not benchmark
numbers. The last stdout line is the JSON result, printed only when
every phase passed. Without a TPU the script exits non-zero and prints
no result.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# proof that the device did the work
# ---------------------------------------------------------------------------

# Counters that mean a device failure was absorbed somewhere on the path.
FORBIDDEN_PREFIXES = ("wgl.ladder.", "elle.ladder.", "scc.ladder.",
                      "fleet.quarantine.added", "fleet.breaker.opened",
                      "scc.device-nonconverged")


class CacheEvents:
    """Counts compile requests that consulted jax's persistent
    compilation cache, and how many of them it served."""

    def __init__(self):
        import jax

        self.counts = {"requests": 0, "hits": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.counts["hits"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def start_phase() -> None:
    from jepsen_tpu import telemetry
    from jepsen_tpu.tpu import profiler

    telemetry.reset()
    profiler.reset()


def device_proof(kernels, results=()) -> dict:
    """Fails unless every kernel in `kernels` launched and nothing on the
    path degraded. Returns the launch counts."""
    from jepsen_tpu import telemetry

    c = telemetry.get().counters()
    bad = {k: v for k, v in c.items()
           if k.startswith(FORBIDDEN_PREFIXES) and v}
    check(not bad, f"device failures absorbed: {bad}")
    launches = {k: c.get(f"profiler.{k}.launches", 0) for k in kernels}
    missing = [k for k, n in launches.items() if not n]
    check(not missing, f"no device launch recorded for {missing} "
                       f"(launches: {launches})")
    degraded = [i for i, r in enumerate(results)
                if isinstance(r, dict) and "degradation" in r]
    check(not degraded, f"results {degraded[:5]} carry degradation")
    return launches


def peak_bytes(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def report(name: str, diag: dict) -> None:
    log(f"phase {name}: " + json.dumps(diag, sort_keys=True))


# ---------------------------------------------------------------------------
# phases (sizes are parameters so a CPU rehearsal can shrink them)
# ---------------------------------------------------------------------------

def timed(fn, *args, **kw):
    t0 = time.monotonic()
    out = fn(*args, **kw)
    return out, round(time.monotonic() - t0, 3)


def lin_inputs(n_ops: int):
    from jepsen_tpu.tpu import synth

    hist = synth.register_history(n_ops, n_procs=5, seed=42)
    bad, at = synth.corrupt_register_history(hist, at_frac=0.85)
    return hist, bad, at


def lin_check(hist) -> dict:
    from jepsen_tpu import checker
    from jepsen_tpu.checker import models

    return checker.linearizable(
        {"model": models.cas_register()}).check({}, hist)


def phase_linearizability(n_ops: int = 500_000) -> dict:
    from jepsen_tpu.tpu import certify

    hist, bad, at = lin_inputs(n_ops)
    start_phase()
    res, first = timed(lin_check, hist)
    check(res["valid?"] is True, f"valid history judged {res['valid?']}")
    res2, warm = timed(lin_check, hist)
    check(res2["valid?"] is True, "second check of the valid history "
                                  f"judged {res2['valid?']}")
    certify.validate(hist, res["certificate"])
    res_bad, wall_bad = timed(lin_check, bad)
    check(res_bad["valid?"] is False,
          f"history corrupted at event {at} judged {res_bad['valid?']}")
    certify.validate(bad, res_bad["certificate"])
    launches = device_proof(("wgl-reach",), (res, res2, res_bad))
    return {"events": len(hist), "first_call_s": first, "wall_s": warm,
            "invalid_wall_s": wall_bad, "analyzer": res.get("analyzer"),
            "invalid_analyzer": res_bad.get("analyzer"),
            "corrupted_event": at, "launches": launches}


def ensemble_inputs(n_hists: int, ops_each: int):
    from jepsen_tpu.tpu import synth

    return [synth.register_history(ops_each, n_procs=4, seed=1000 + i,
                                   crash_p=0.15)
            for i in range(n_hists)]


def ensemble_check(hists, certify: bool = False) -> list:
    from jepsen_tpu.checker import models
    from jepsen_tpu.tpu import wgl

    return wgl.analysis_batch_streamed(models.cas_register(), hists,
                                       chunk=128, certify=certify)


def phase_ensemble(n_hists: int = 1024, ops_each: int = 400,
                   n_host: int = 64) -> dict:
    from jepsen_tpu.checker import models
    from jepsen_tpu.tpu import wgl

    hists = ensemble_inputs(n_hists, ops_each)
    start_phase()
    res, first = timed(ensemble_check, hists)
    res2, warm = timed(ensemble_check, hists)
    check([r["valid?"] for r in res] == [r["valid?"] for r in res2],
          "ensemble verdicts changed between two identical checks")
    subset = sorted(random.Random(7).sample(range(n_hists), n_host))
    model = models.cas_register()
    t0 = time.monotonic()
    for i in subset:
        ref = wgl.analysis(model, hists[i], algorithm="wgl")
        check(ref["valid?"] == res[i]["valid?"],
              f"history {i}: device {res[i]['valid?']} vs host "
              f"{ref['valid?']}")
    host_s = round(time.monotonic() - t0, 3)
    n_valid = sum(1 for r in res if r["valid?"] is True)
    launches = device_proof(("wgl",), res + res2)
    return {"histories": n_hists, "events": sum(len(h) for h in hists),
            "valid": n_valid, "first_call_s": first, "wall_s": warm,
            "host_subset": len(subset), "host_subset_s": host_s,
            "launches": launches}


def g1c_pair(hist):
    """The history plus two concurrent txns that each read the other's
    append: a write-read cycle (G1c) no serial order explains. Unlike
    the read corruptors in tests/test_elle_device.py it always makes a
    cycle, so the device SCC must find it."""
    from jepsen_tpu.history import History, op

    ops = [op(**o.to_dict()) for o in hist]
    n = len(ops)
    ka, kb = "smoke-g1c-a", "smoke-g1c-b"
    va, vb = 10**9, 10**9 + 1
    t1 = [["append", ka, va], ["r", kb, None]]
    t2 = [["append", kb, vb], ["r", ka, None]]
    ops += [op(index=n, time=n, type="invoke", process=1000, f="txn",
               value=t1),
            op(index=n + 1, time=n + 1, type="invoke", process=1001,
               f="txn", value=t2),
            op(index=n + 2, time=n + 2, type="ok", process=1000, f="txn",
               value=[["append", ka, va], ["r", kb, [vb]]]),
            op(index=n + 3, time=n + 3, type="ok", process=1001, f="txn",
               value=[["append", kb, vb], ["r", ka, [va]]])]
    return History(ops, assign_indices=False)


def elle_check(hist, engine: str | None = None) -> dict:
    from jepsen_tpu.tpu import elle

    return elle.check_list_append(
        hist, {"engine": engine} if engine else None)


def elle_inputs(n_txns: int):
    from jepsen_tpu.tpu import synth

    hist = synth.list_append_history(n_txns, seed=11)
    return hist, g1c_pair(hist)


def phase_elle(n_txns: int = 100_000) -> dict:
    from jepsen_tpu import telemetry

    hist, bad = elle_inputs(n_txns)
    start_phase()
    dev, first = timed(elle_check, hist)
    dev2, warm = timed(elle_check, hist)
    dev_bad, bad_s = timed(elle_check, bad)
    scc_device = telemetry.get().counters().get("scc.path.device", 0)
    check(scc_device > 0, "no SCC ran on the device")
    launches = device_proof(("scc",), (dev, dev2, dev_bad))
    host, host_s = timed(elle_check, hist, "host")
    host_bad = elle_check(bad, "host")
    for name, d, h in (("valid", dev, host), ("g1c", dev_bad, host_bad)):
        check(d["valid?"] == h["valid?"]
              and d["anomaly-types"] == h["anomaly-types"],
              f"{name} history: device {d['valid?']} "
              f"{d['anomaly-types']} vs host {h['valid?']} "
              f"{h['anomaly-types']}")
    check(dev["valid?"] is True, f"valid history: {dev['anomaly-types']}")
    check(dev_bad["valid?"] is False and "G1c" in dev_bad["anomaly-types"],
          f"seeded G1c not flagged: {dev_bad['anomaly-types']}")
    return {"txns": n_txns, "first_call_s": first, "wall_s": warm,
            "g1c_wall_s": bad_s, "host_wall_s": host_s,
            "g1c_types": dev_bad["anomaly-types"],
            "scc_path_device": scc_device, "launches": launches}


def phase_fleet(n_runs: int = 8, ops_each: int = 3000,
                chunk: int = 512) -> dict:
    from jepsen_tpu.checker import models
    from jepsen_tpu.fleet import client as fclient
    from jepsen_tpu.fleet import scheduler as fsched
    from jepsen_tpu.fleet import server as fserver
    from jepsen_tpu.tpu import certify, synth, wgl

    hists = [synth.register_history(ops_each, seed=3000 + i)
             for i in range(n_runs)]
    model = models.cas_register()
    start_phase()
    solo = [wgl.analysis(model, h) for h in hists]
    out: dict = {}
    errors: list = []
    with tempfile.TemporaryDirectory(prefix="chip-smoke-fleet-") as base:
        srv = fserver.FleetServer(
            base, scheduler=fsched.Scheduler(window_s=0.1),
            quotas=fserver.Quotas(max_tenants=n_runs + 1,
                                  max_total_streams=2 * n_runs),
            stream_checks=False).start()
        try:
            def tenant(i):
                try:
                    c = fclient.FleetClient(srv.addr, f"smoke{i}", "r",
                                            model="cas-register")
                    try:
                        ops = list(hists[i])
                        for j in range(0, len(ops), chunk):
                            c.send_chunk(ops[j:j + chunk])
                        out[i] = c.finish(timeout_s=600)
                    finally:
                        c.close()
                except Exception as e:  # noqa: BLE001 — reported below
                    errors.append((i, repr(e)))

            t0 = time.monotonic()
            threads = [threading.Thread(target=tenant, args=(i,))
                       for i in range(n_runs)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            wall = round(time.monotonic() - t0, 3)
            check(not any(t.is_alive() for t in threads),
                  "fleet tenants still running after 900 s")
            st = srv.stats()
        finally:
            srv.stop()
    check(not errors, f"fleet tenants failed: {errors}")
    verdicts = [out[i]["result"] for i in range(n_runs)]
    for i, (v, s) in enumerate(zip(verdicts, solo)):
        check(v["valid?"] == s["valid?"],
              f"tenant {i}: fleet {v['valid?']} vs solo {s['valid?']}")
        if "certificate" in v:
            certify.validate(hists[i], v["certificate"])
    sch = st["scheduler"]
    check(not sch["breaker_open"], "fleet device breaker opened")
    check(not sch["quarantine"], f"runs quarantined: {sch['quarantine']}")
    launches = device_proof(("wgl",), verdicts + solo)
    return {"tenants": n_runs, "events": sum(len(h) for h in hists),
            "wall_s": wall, "final_launches": sch.get("final_launches"),
            "slice_launches": sch.get("slice_launches"),
            "launches": launches}


# ---------------------------------------------------------------------------
# four chips vs one
# ---------------------------------------------------------------------------

def _comparable(res) -> object:
    """What must not depend on the mesh: verdicts, certificates and
    anomalies (search-shape stats and timings may)."""
    if isinstance(res, list):
        return [_comparable(r) for r in res]
    keep = ("valid?", "certificate", "anomaly-types", "anomalies")
    return json.dumps({k: res.get(k) for k in keep}, sort_keys=True,
                      default=repr)


def sharded_runs(n_chips: int, lin_ops: int = 500_000,
                 n_hists: int = 1024, ops_each: int = 400,
                 elle_txns: int = 100_000) -> dict:
    import jax

    from jepsen_tpu import telemetry
    from jepsen_tpu.tpu import profiler

    hist, _bad, _at = lin_inputs(lin_ops)
    hists = ensemble_inputs(n_hists, ops_each)
    elle_hist, elle_bad = elle_inputs(elle_txns)
    checks = {
        "linearizability": (lambda: lin_check(hist), ("wgl-reach",)),
        "ensemble": (lambda: ensemble_check(hists, certify=True),
                     ("wgl",)),
        "elle": (lambda: [elle_check(elle_hist), elle_check(elle_bad)],
                 ("scc",)),
    }
    walls: dict = {}
    results: dict = {}
    peaks = None
    for cap in (None, "1"):
        label = f"mesh{n_chips}" if cap is None else "mesh1"
        if cap is None:
            os.environ.pop("JEPSEN_TPU_SPMD_DEVICES", None)
        else:
            os.environ["JEPSEN_TPU_SPMD_DEVICES"] = cap
        for name, (fn, kernels) in checks.items():
            start_phase()
            res, first = timed(fn)
            res, warm = timed(fn)
            flat = res if isinstance(res, list) else [res]
            launches = device_proof(kernels, flat)
            g = telemetry.get().gauges()
            devs = max([g.get(f"profiler.{k}.devices", 1)
                        for k in kernels] + [g.get("scc.spmd.devices", 1)])
            if cap is None:
                check(devs == n_chips, f"{name}: sharded launches spanned "
                                       f"{devs} devices, not {n_chips}")
            else:
                check(devs == 1, f"{name}: mesh cap 1 still sharded over "
                                 f"{devs} devices")
            walls.setdefault(name, {})[label] = warm
            results.setdefault(name, {})[label] = _comparable(res)
            report(f"{name}[{label}]",
                   {"first_call_s": first, "wall_s": warm,
                    "mesh_devices": devs, "launches": launches})
        if cap is None:
            # read before any single-device run adds to device 0
            peaks = peak_bytes(jax.devices()[:n_chips])
            log(f"per-device peak_bytes_in_use after the mesh{n_chips} "
                f"runs: {peaks}")
    os.environ.pop("JEPSEN_TPU_SPMD_DEVICES", None)
    for name, by_mesh in results.items():
        a, b = by_mesh[f"mesh{n_chips}"], by_mesh["mesh1"]
        check(a == b, f"{name}: verdicts/certificates differ between "
                      f"mesh{n_chips} and mesh1")
    check(peaks and all(isinstance(p, int) and p > 0 for p in peaks),
          f"device memory stats unavailable: {peaks}")
    check(min(peaks[1:]) >= 0.1 * peaks[0],
          f"data sits on device 0, not spread over the mesh: {peaks}")
    eff = {name: profiler.parallel_efficiency(
        {1: w["mesh1"], n_chips: w[f"mesh{n_chips}"]})
        for name, w in walls.items()}
    for name, e in eff.items():
        profiler.check_efficiency(
            e, log=lambda m, name=name: log(f"WARN {name}: {m}"))
    log("parallel_efficiency (smoke diagnostics, not a benchmark): "
        + json.dumps({n: e.get(n_chips) for n, e in eff.items()}))
    return {"walls": walls, "peaks": peaks}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths, 4 chips vs 1")
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "jepsen_tpu")):
        print(f"chip_smoke: no jepsen_tpu package next to {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, here)

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU: jax found no backend ({e})",
              file=sys.stderr)
        return 3
    platforms = sorted({d.platform for d in devices})
    if platforms != ["tpu"]:
        print(f"chip_smoke: no TPU: jax found {len(devices)} "
              f"device(s) on {platforms} ({devices[0].device_kind}); "
              "refusing to run on anything else", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 3
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")

    from jepsen_tpu.tpu import spmd

    cache_dir = spmd.enable_compile_cache()
    warm = os.path.isdir(cache_dir) and any(os.scandir(cache_dir))
    log(f"compile cache: {cache_dir} ({'warm' if warm else 'cold'} at "
        "start)")
    cache = CacheEvents()

    if args.chips == 1:
        phases = (("linearizability", phase_linearizability),
                  ("ensemble", phase_ensemble),
                  ("elle", phase_elle),
                  ("fleet", phase_fleet))
    else:
        phases = (("sharded", lambda: sharded_runs(args.chips)),)
    t_all = time.monotonic()
    for name, fn in phases:
        before = cache.snapshot()
        t0 = time.monotonic()
        try:
            diag = fn()
        except Exception as e:  # noqa: BLE001 — any failure fails the run
            import traceback

            traceback.print_exc()
            log(f"phase {name} FAILED: {e!r}")
            return 1
        after = cache.snapshot()
        diag["phase_s"] = round(time.monotonic() - t0, 3)
        diag["cache_hits"] = after["hits"] - before["hits"]
        diag["cache_misses"] = (after["requests"] - before["requests"]
                                - diag["cache_hits"])
        diag["peak_bytes_in_use"] = peak_bytes(devices[:args.chips])
        report(name, diag)
    log(f"all phases passed in {time.monotonic() - t_all:.1f}s "
        "(smoke-run diagnostics, not benchmark numbers)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
