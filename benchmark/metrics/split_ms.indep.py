"""Host time splitting each independent-key history by key and stamping
each key's certificate with its key and the whole history's digest (the
independent.split and independent.stamp spans) per million history
events decided."""

from benchlib import readers


def read(cell):
    ns = cell.span_ns("independent.split") + cell.span_ns(
        "independent.stamp")
    return readers.per_mevent(cell, ns / 1e6 if ns else None)
