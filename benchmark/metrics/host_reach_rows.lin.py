"""Rows the device left to the exact host search (the counter
wgl.host-reach.rows, one per wgl.search_host_reach call) per million
history events decided. None where the program keeps no such counter."""

from benchlib import readers


def read(cell):
    n = cell.counters.get("wgl.host-reach.rows")
    return None if n is None else readers.per_mevent(cell, float(n))
