"""The share, in %, of a certificate's segment orders that were ready
from the prefetch (counted while the device ran a wave's main launch):
the counters certify.segments.prefetched over certify.segments. None
where the program keeps no such counter."""


def read(cell):
    n = cell.counters.get("certify.segments")
    if n is None:
        return None
    if not n:
        return 0.0
    return 100.0 * cell.counters.get("certify.segments.prefetched", 0) / n
