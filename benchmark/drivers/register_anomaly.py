"""One register history with one stale read per verdict through
checker.linearizable, the path core.analyze takes for a single-register
test that finds a bug: the segmented WGL search stops at the wave that
holds the anomaly (segment early exit), and the verdict carries an
invalid certificate (the witness prefix and the stuck op).

Parameters: register_stream's (n_ops, n_procs, n_values, crash_p,
warmup_history_seed, history_seeds, kernels) and plant_at, the share of
the events at or after which the stale read is planted in each base
(benchlib/stale.py). Each history checked is a variant of its planted
base drawn from --seed (registers.variant), which keeps the plant
stale. Set-up checks the warm-up's base valid and then planted, so
that early exit and the witness extraction compile nothing in the
window. The log gives `wgl.segmented.early-exit` for each verdict.
"""

from __future__ import annotations

from benchlib import harness, registers, stale


def check_history(hist):
    from jepsen_tpu import checker
    from jepsen_tpu.checker import models

    return checker.linearizable(
        {"model": models.cas_register()}).check({}, hist)


def planted_base(cell, hs):
    events, witness = registers.base(cell.params, hs)
    events, pos = stale.plant(events, witness, cell.params["plant_at"])
    cell.log(f"base {hs}: stale read planted at event {pos} of "
             f"{len(events)}")
    return events, witness


def run(cell) -> dict:
    from jepsen_tpu import telemetry

    p = cell.params
    seeds = p["history_seeds"]
    hs = p["warmup_history_seed"]
    events, witness = registers.base(p, hs)
    cell.phase("generation of the warm-up's base")
    check_history(registers.program_input(
        (registers.variant(p, events, cell.seed, "warm-up"), witness)))
    cell.phase("warm-up verdict, valid")
    events, witness = planted_base(cell, hs)
    check_history(registers.program_input(
        (registers.variant(p, events, cell.seed, "warm-up planted"),
         witness)))
    del events, witness
    cell.phase("warm-up verdict, planted")
    bases: dict = {}
    kept = cell.kept = []
    exits = [0]

    def prepare(j):
        hs = seeds[j % len(seeds)]
        if hs not in bases:
            bases[hs] = planted_base(cell, hs)
        events, witness = bases[hs]
        ev = registers.variant(p, events, cell.seed, j)
        return (ev, witness), {"events": len(ev)}

    def keep(_rec, data, out):
        n = telemetry.get().counters().get("wgl.segmented.early-exit", 0)
        cell.log(f"verdict {len(kept)}: valid? {(out or {}).get('valid?')!r}"
                 f", wgl.segmented.early-exit {n - exits[0]}")
        exits[0] = n
        kept.append((data[0], data[1], out))

    harness.sequential_window(cell, prepare, registers.program_input,
                              check_history, keep)
    done = sum(r["events"] for r in cell.records if not r["failed"])
    return {"lin_events_per_s": done / cell.window_s}


def compare(cell) -> None:
    registers.compare(cell, cell.kept)
