"""Host time extracting the keys' verdict certificates (the
certify.attach spans) per million history events decided: the
independent-key cell."""

from benchlib import readers


def read(cell):
    ns = cell.span_ns("certify.attach")
    return readers.per_mevent(cell, ns / 1e6 if ns else None)
