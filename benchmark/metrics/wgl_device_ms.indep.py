"""Device time of the WGL search programs per million history events
decided, by their jit names in the trace (`jit__kernel` on one chip,
`jit_run` for the SPMD program): the independent-key cell."""

from benchlib import readers

PROGRAMS = ("jit__kernel", "jit_run")


def read(cell):
    return readers.per_mevent(cell, readers.device_ms(cell, PROGRAMS))
