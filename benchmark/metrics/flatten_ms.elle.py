"""Host time flattening list-append transactions into the device
engine's int arrays (the elle.flatten spans: the C flattener, or its
Python fallback) per million history events decided."""

from benchlib import readers


def read(cell):
    ns = cell.span_ns("elle.flatten")
    return readers.per_mevent(cell, ns / 1e6 if ns else None)
