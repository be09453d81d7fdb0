"""XLA programs: of the rows a user's window function ran over in the
window, the share whose every sort the Pallas sorting network made
(``fn_rows_sorted`` over ``rows`` of the launch records that ran a user's
function, ``tile_elems`` above 0, and whose result reached the host in
the window; ``windflow_tpu/telemetry/spans.Launch``, docs/RUNTIME.md
5d): 1 on the TPU where the function sorts its windows at widths of 128
to 8,192; under 1 where blocks held -0.0, a NaN or a subnormal and took
``lax.sort``; 0 where XLA sorts every block, or the function does not
sort.  Nothing on a program whose launch records do not count it, or
where no launch ran a user's function."""
from benchmarks.harness import program_spans


def read(rec):
    recs = program_spans._launches(rec)
    if not recs or not all(hasattr(r, "fn_rows_sorted") for r in recs):
        return None
    fn = [r for r in recs if r.tile_elems]
    rows = sum(r.rows for r in fn)
    return sum(r.fn_rows_sorted for r in fn) / rows if rows else None
