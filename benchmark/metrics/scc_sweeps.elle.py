"""Fixpoint sweeps of the SCC kernel, forward plus backward (the
program's scc.sweeps counter, counted by the kernel itself), per verdict
of the window. None where the program has no such counter."""


def read(cell):
    n = cell.counters.get("scc.sweeps")
    if not n or not cell.records:
        return None
    return float(n) / len(cell.records)
