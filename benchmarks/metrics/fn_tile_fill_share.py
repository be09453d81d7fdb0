"""XLA programs: of the tile a user's window function ran over in the
window, the share that was the windows' own elements (``fn_elems`` over
``tile_elems`` of the launch records whose result reached the host in
the window, ``windflow_tpu/telemetry/spans.Launch``): the rest is the
padding of each window to the launch's power-of-two width and the rows
of the last block the launch did not fill, which the function sorts or
sums all the same.  Nothing on a program whose launch records do not
count them, or where no launch ran a user's function."""
from benchmarks.harness import program_spans


def read(rec):
    recs = program_spans._launches(rec)
    if not recs or not all(hasattr(r, "tile_elems") for r in recs):
        return None
    tile = sum(r.tile_elems for r in recs)
    return sum(r.fn_elems for r in recs) / tile if tile else None
