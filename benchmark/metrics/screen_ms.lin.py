"""Time of the segmented search's prefix screen (the wgl.screen spans:
its packing, launch, drain and host rows, once per wave) per million
history events decided. Beside wgl_device_ms.lin it splits the search
between the screen and the waves' main launches."""

from benchlib import readers


def read(cell):
    ns = cell.span_ns("wgl.screen")
    return readers.per_mevent(cell, ns / 1e6 if ns else None)
