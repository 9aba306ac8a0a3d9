"""Share of the traced window in which no program ran on the chip,
mean over the cell's chips: the independent-key cell."""

from benchlib import readers


def read(cell):
    return readers.idle_percent(cell)
