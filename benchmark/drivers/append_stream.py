"""One list-append history per verdict through cycle.append_checker, the
path core.analyze takes for jepsen.tests.cycle.append's checker: engine
`auto` (the device engine at this size) and a certificate on every
verdict of the window.

Parameters: n_txns, concurrency, key_count, min_txn_length,
max_txn_length, max_writes_per_key, info_p, fail_p (the generator's),
warmup_history_seed (the base history set-up checks), history_seeds
(verdict j of the window checks base j, cycling), kernels (launch sites
that must run). Each history checked is a variant of its base drawn
from --seed (appends.variant: keys, values and process ids renamed, the
event order kept), so every seed asks for the same work, down to the
SCC kernel's sweeps. The mix names bases whose SCC shape bucket is the
warm-up's (PERF.md says how they were found), so nothing compiles in
the window.

The warm-up verdict is the same call without its certificate: the
certificate compiles nothing, and leaving it out keeps set-up short on
a program whose certificate is slow. After the window the SCC launch
records are kept on the cell (`scc_launches`) for the roofline reader.
"""

from __future__ import annotations

import gc

from benchlib import appends, harness


def check_history(hist, opts=None):
    from jepsen_tpu.checker import cycle

    return cycle.append_checker(opts or {}).check({}, hist)


def scc_launches():
    from jepsen_tpu.tpu import profiler

    return [r for r in profiler.get().records() if r["kernel"] == "scc"]


def log_launches(cell, what, launches) -> None:
    cell.log(f"scc launches of the {what} (nodes, edges, bucket, sweeps, "
             "rounds): " + "; ".join(
                 f"{r.get('nodes')}, {r.get('edges')}, {r.get('bucket')}, "
                 f"{r.get('sweeps')}, {r.get('rounds')}" for r in launches))


def run(cell) -> dict:
    p = cell.params
    seeds = p["history_seeds"]
    events, witness = appends.base(p, p["warmup_history_seed"])
    cell.phase("generation of the warm-up's base")
    ev = appends.variant(p, events, cell.seed, "warm-up")
    gc.freeze()  # the collector need not rescan the benchmark's lists
    hist = appends.program_input((ev, witness))
    del events, witness, ev
    cell.phase("warm-up variant and History")
    check_history(hist, {"certify": False})
    del hist
    cell.phase("warm-up verdict")
    log_launches(cell, "warm-up", scc_launches())
    bases: dict = {}
    kept = cell.kept = []

    def prepare(j):
        hs = seeds[j % len(seeds)]
        if hs not in bases:
            bases[hs] = appends.base(p, hs)
        events, witness = bases[hs]
        ev = appends.variant(p, events, cell.seed, j)
        return (ev, witness), {"events": len(ev)}

    def keep(_rec, data, out):
        kept.append((data[0], data[1], out))

    harness.sequential_window(cell, prepare, appends.program_input,
                              check_history, keep)
    cell.scc_launches = scc_launches()
    log_launches(cell, "window", cell.scc_launches)
    cell.log("engine counters: " + str({
        k: v for k, v in cell.counters.items()
        if k.startswith(("elle.flatten.", "elle.host-engine"))}))
    stages: dict = {}
    for name, t0, t1 in cell.spans:
        if name.startswith(("elle.", "certify.")):
            stages[name] = stages.get(name, 0.0) + (t1 - t0) / 1e9
    cell.log("stage seconds in the window: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(stages.items())))
    done = sum(r["events"] for r in cell.records if not r["failed"])
    return {"lin_events_per_s": done / cell.window_s}


def compare(cell) -> None:
    appends.compare(cell, cell.kept)
