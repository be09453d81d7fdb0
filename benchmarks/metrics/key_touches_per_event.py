"""Host operators and native pane fold: the keys the window engine's
calls visited inside the window (a key once a call: ``key_touches`` of
the program's counter series, ``windflow_tpu/telemetry/spans.Counters``,
cut at the window to a note and a 100 ms bucket at each end) over the
events handed to the graph in it.  How exposed a cell is to where a key
state lives: a touch is a key's first tuple of a call, which on a table
beyond the caches is a trip to memory (0.002 with 111 keys a chunk of
65,536, 0.1 with 7,000).  Nothing on a program whose engine does not
count it."""
from benchmarks.harness import program_spans


def touched(rec):
    """(key touches, those in a call that ran ahead) inside the window,
    summed over the graph's window operators; None where the program
    keeps no such series."""
    g = program_spans.graph_of(rec)
    total = None
    for c in list((getattr(g, "counters", None) or {}).values()):
        if not hasattr(c, "touched_between"):
            return None
        t, a = c.touched_between(*program_spans.window_of(rec))
        total = (t, a) if total is None else (total[0] + t, total[1] + a)
    return total


def read(rec):
    got = touched(rec)
    if got is None or not got[0] or not rec.get("events"):
        return None               # a store that visits no key counts none
    return got[0] / rec["events"]
