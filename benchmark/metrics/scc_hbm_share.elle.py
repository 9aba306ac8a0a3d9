"""The SCC kernel's share, in %, of its HBM roofline: the bytes its
fixpoint sweeps must at least move (benchlib.scc_work, from each
launch's sweep count and shape bucket) over its device time (the
`jit_scc_peel` program in the trace) at the chip's published HBM
bandwidth (peaks.json). None where the launches carry no sweep count or
the trace shows no such program."""

from benchlib import scc_work


def read(cell):
    b = scc_work.launch_bytes(getattr(cell, "scc_launches", None))
    s = cell.program_device_s(scc_work.PROGRAMS)
    if not b or not s:
        return None
    bw = scc_work.hbm_bytes_per_s(cell.devices[0].device_kind)
    return 100.0 * b / (s * bw)
