"""The bytes the SCC kernel (the program's `tpu/scc.py`, Orzan's
colouring as nested fixpoint loops) must at least move, for its HBM
roofline.

Each fixpoint sweep, forward or backward, streams the edge list's source
and destination indices (int32 each) and its edge mask (one byte), and
reads and writes the colour array (int32) of the launch's padded nodes:
9 bytes an edge of e_pad and 8 bytes a node of n_pad. The gathers and
scatters a sweep also makes are left out, whatever the compiler keeps
on chip, so the count is a lower bound and a share of the roofline
computed from it cannot exceed what the chip moved.
"""

from __future__ import annotations

import ast
import json
import os

EDGE_BYTES = 9
NODE_BYTES = 8

# the kernel's program in a device trace: scc._scc_program's scc_peel
PROGRAMS = ("jit_scc_peel",)

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def sweep_bytes(n_pad: int, e_pad: int, n_dev: int = 1) -> int:
    """Bytes one sweep moves at least on each chip of the launch (a
    sharded launch streams its share of the edges and the whole colour
    array on every chip)."""
    return EDGE_BYTES * e_pad // n_dev + NODE_BYTES * n_pad


def bucket(rec: dict) -> tuple[int, int, int]:
    """(n_pad, e_pad, chips) of a profiler launch record's shape bucket:
    ('scc', n_pad, e_pad) or ('scc-sharded', chips, n_pad, e_pad)."""
    b = ast.literal_eval(rec["bucket"])
    if b[0] == "scc-sharded":
        return int(b[2]), int(b[3]), int(b[1])
    return int(b[1]), int(b[2]), 1


def launch_bytes(records) -> int | None:
    """Bytes per chip the launches' sweeps must at least move: each
    launch's sweep count (the kernel's own, on its profiler record)
    times its bucket's sweep bytes. None where no launch carries a
    sweep count."""
    counted = [r for r in records or () if r.get("sweeps") is not None]
    if not counted:
        return None
    return sum(int(r["sweeps"]) * sweep_bytes(*bucket(r)) for r in counted)


def hbm_bytes_per_s(device_kind: str) -> float:
    """The chip's published HBM bandwidth; KeyError for a device kind
    peaks.json does not list."""
    with open(PEAKS) as f:
        return float(json.load(f)[device_kind]["hbm_bytes_per_s"])
