"""Host time extracting verdict certificates (the certify.attach span)
per million history events decided: the elle cells. None where the
program opens no certify.attach span for an elle verdict."""

from benchlib import readers


def read(cell):
    ns = cell.span_ns("certify.attach")
    return readers.per_mevent(cell, ns / 1e6 if ns else None)
