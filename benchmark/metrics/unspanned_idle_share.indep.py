"""Share of the traced window in which the first chip was idle while no
program span was open (the trace reduction's `(no program span)` gaps),
in percent: host work inside an independent-key verdict that the
program does not name. 0.0 where every idle gap fell inside some
span."""

UNNAMED = "(no program span)"


def read(cell):
    t = cell.trace_summary
    if not t or not t.get("window_s"):
        return None
    return 100.0 * t["idle_by_span"].get(UNNAMED, 0.0) / t["window_s"]
