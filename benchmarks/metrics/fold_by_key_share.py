"""Host operators and native pane fold: of the tuples the window engine
folded inside the window, the share it folded with their key's other
tuples of the chunk in one combine (``folded_by_key`` over
``folded_by_key + folded_singly`` of the program's counter series,
``windflow_tpu/telemetry/spans.Counters``).  1.0 where every chunk lies
in one pane; a chunk that straddles a pane edge, a late tuple or a lane
that keeps more than a combine carries folds one by one.  Nothing on a
program whose engine does not count it."""
from benchmarks.harness import program_spans


def read(rec):
    g = program_spans.graph_of(rec)
    by_key = singly = 0
    for c in list((getattr(g, "counters", None) or {}).values()):
        if not hasattr(c, "folded_between"):
            return None
        k, s = c.folded_between(*program_spans.window_of(rec))
        by_key, singly = by_key + k, singly + s
    return by_key / (by_key + singly) if by_key + singly else None
