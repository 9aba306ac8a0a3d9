"""Host time encoding the keys' histories and packing them for the one
batched WGL launch (the batch path's wgl.encode and wgl.pack spans) per
million history events decided."""

from benchlib import readers


def read(cell):
    ns = cell.span_ns("wgl.encode") + cell.span_ns("wgl.pack")
    return readers.per_mevent(cell, ns / 1e6 if ns else None)
