"""Host time of the exact host search over the rows the device left to
it (the wgl.host-reach spans) per million history events decided; 0.0
where no row fell to it, None where the program keeps no row counter."""

from benchlib import readers


def read(cell):
    n = cell.counters.get("wgl.host-reach.rows")
    if n is None:
        return None
    ms = cell.span_ns("wgl.host-reach") / 1e6 if n else 0.0
    return readers.per_mevent(cell, ms)
