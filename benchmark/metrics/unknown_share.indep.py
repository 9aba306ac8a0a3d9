"""Share, in %, of the batch launch's rows that the kernel returned
UNKNOWN (window or frontier overflow), left to the host search: the
counters wgl.batch.unknown over wgl.batch.rows. None where the program
keeps no such counters."""


def read(cell):
    rows = cell.counters.get("wgl.batch.rows")
    if not rows:
        return None
    return 100.0 * cell.counters.get("wgl.batch.unknown", 0) / rows
