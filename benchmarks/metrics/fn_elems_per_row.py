"""XLA programs: the elements a user's window function was handed a
result row: ``fn_elems`` over ``rows`` of the launch records whose
result reached the host in the window
(``windflow_tpu/telemetry/spans.Launch``: the sum of the launch's
extents' lengths, and its windows).  About 3,600 where a plug's function
reads its whole hour of one-second readings every second; a function
handed less a row (an incremental median) reads lower.  Nothing on a
program whose launch records do not count them, or where no launch ran
a user's function."""
from benchmarks.harness import program_spans


def read(rec):
    recs = program_spans._launches(rec)
    if not recs or not all(hasattr(r, "fn_elems") for r in recs):
        return None
    elems = sum(r.fn_elems for r in recs)
    return elems / sum(r.rows for r in recs) if elems else None
