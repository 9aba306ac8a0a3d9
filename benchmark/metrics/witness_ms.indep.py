"""Host time on the members the batch launch did not decide VALID: the
witness extraction of INVALID rows, the host search of UNKNOWN rows and
the model search of unencodable members (the wgl.batch.witness spans)
per million history events decided; 0.0 where no member fell to it,
None where the program keeps no batch row counter."""

from benchlib import readers


def read(cell):
    if cell.counters.get("wgl.batch.rows") is None:
        return None
    return readers.per_mevent(
        cell, cell.span_ns("wgl.batch.witness") / 1e6)
