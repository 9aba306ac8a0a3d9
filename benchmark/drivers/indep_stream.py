"""One independent-key register history per verdict through
`independent.checker(checker.linearizable(...))`, the checker of
jepsen.tests.linearizable-register: the history split by key, every
key decided in one batched launch of the WGL kernel, undecided members
searched on the host, a certificate on every key's verdict.

Parameters: n_ops, concurrency, group_size, readers, n_values,
process_limit, per_key_limit, nemesis_ops, info_p_partitioned,
info_p_healed, info_effect_p (the generator's, benchlib/indep.py),
warmup_history_seed (the base history set-up checks), history_seeds
(verdict j of the window checks base j, cycling), kernels (launch sites
that must run). Each history checked is a variant of its base drawn
from --seed (indep.variant: keys, each key's values and the process ids
renamed, the event order kept), so every seed launches the same rows
and shapes and leaves the same members to the host. The mix names
bases with the warm-up's key count, so their launch has the warm-up's
shape and nothing compiles in the window (PERF.md says how they were
found).

The run fails after the warm-up where the program keeps no
`independent.keys` or `wgl.batch.rows` counter: without them it
cannot show that its verdicts came from the batch launch and not from
per-key checks after a failed one.
"""

from __future__ import annotations

import statistics

from benchlib import harness, indep, registers

REQUIRED = ("independent.keys", "wgl.batch.rows")


def check_history(hist):
    from jepsen_tpu import checker, independent
    from jepsen_tpu.checker import models

    return independent.checker(checker.linearizable(
        {"model": models.cas_register()})).check({}, hist)


def describe(cell, what, events) -> None:
    st = indep.key_stats(events)
    ops = sorted(a for a, _c, _p in st.values())
    crashed = sorted(c for _a, c, _p in st.values())
    q = statistics.quantiles
    cell.log(f"{what}: {len(events)} events, {len(st)} keys; ops a key "
             f"min {ops[0]} quartiles {q(ops, n=4)} max {ops[-1]}; "
             f"crashed a key quartiles {q(crashed, n=4)} max "
             f"{crashed[-1]}, {sum(crashed)} in all")


def run(cell) -> dict:
    from jepsen_tpu import telemetry

    p = cell.params
    seeds = p["history_seeds"]
    events, witness = indep.base(p, p["warmup_history_seed"])
    cell.phase("generation of the warm-up's base")
    describe(cell, f"warm-up base {p['warmup_history_seed']}", events)
    check_history(registers.program_input(
        (indep.variant(p, events, cell.seed, "warm-up"), witness)))
    del events, witness
    cell.phase("warm-up verdict")
    counters = telemetry.get().counters()
    missing = [n for n in REQUIRED if n not in counters]
    if missing:
        raise RuntimeError(f"the program keeps no {missing} counter: "
                           "its verdicts cannot be told from per-key "
                           "checks after a failed batch")
    bases: dict = {}
    kept = cell.kept = []

    def prepare(j):
        hs = seeds[j % len(seeds)]
        if hs not in bases:
            bases[hs] = indep.base(p, hs)
            describe(cell, f"window base {hs}", bases[hs][0])
        events, witness = bases[hs]
        ev = indep.variant(p, events, cell.seed, j)
        return (ev, witness), {"events": len(ev)}

    def keep(_rec, data, out):
        kept.append((data[0], data[1], out))

    harness.sequential_window(cell, prepare, registers.program_input,
                              check_history, keep)
    cell.log("batch counters: " + str({
        k: v for k, v in sorted(cell.counters.items())
        if k.startswith(("independent.", "wgl.batch.", "certify."))}))
    stages: dict = {}
    for name, t0, t1 in cell.spans:
        if name.startswith(("independent.", "wgl.", "certify.")):
            stages[name] = stages.get(name, 0.0) + (t1 - t0) / 1e9
    cell.log("stage seconds in the window: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(stages.items())))
    done = sum(r["events"] for r in cell.records if not r["failed"])
    return {"lin_events_per_s": done / cell.window_s}


def compare(cell) -> None:
    indep.compare(cell, cell.kept)
    cell.check("batch_fallbacks",
               cell.counters.get("independent.batch-fallback", 0), 0)
