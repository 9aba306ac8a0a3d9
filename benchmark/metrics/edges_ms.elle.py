"""Host time inferring the dependency graph per million history events
decided: the elle.edges spans (writer resolution, version orders, read
anomalies, ww/wr/rw edges) plus the elle.order-edges spans (process
chains and the realtime sweep)."""

from benchlib import readers


def read(cell):
    ns = cell.span_ns("elle.edges") + cell.span_ns("elle.order-edges")
    return readers.per_mevent(cell, ns / 1e6 if ns else None)
