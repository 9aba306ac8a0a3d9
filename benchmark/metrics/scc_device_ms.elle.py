"""Device time of the SCC kernel per million history events decided, by
its jit name in the trace (`scc._scc_program`'s `scc_peel`, seen as
`jit_scc_peel`). None where the trace shows no program of that name."""

from benchlib import readers, scc_work


def read(cell):
    return readers.per_mevent(cell,
                              readers.device_ms(cell, scc_work.PROGRAMS))
