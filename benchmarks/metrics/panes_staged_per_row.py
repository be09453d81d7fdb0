"""Host operators and native pane fold: the pane partials the window
engine's ``flush`` copied into launch buffers inside the window over the
windows it staged there (``panes_staged`` / ``windows_staged`` of the
program's counter series, ``windflow_tpu/telemetry/spans.Counters``, cut
at the window to a note and a 100 ms bucket at each end).  2 where a
window is two panes and a key stages one window a launch; 117 where a
window is 3,600 panes and a launch stages 31 windows of a key over one
span of 3,630.  Nothing on a program whose engine does not count them."""
from benchmarks.harness import program_spans


def staged(rec):
    """(panes, windows) staged inside the window, summed over the graph's
    window operators; None where the program keeps no such series."""
    g = program_spans.graph_of(rec)
    total = None
    for c in list((getattr(g, "counters", None) or {}).values()):
        if not hasattr(c, "staged_between"):
            return None
        p, w = c.staged_between(*program_spans.window_of(rec))
        total = (p, w) if total is None else (total[0] + p, total[1] + w)
    return total


def read(rec):
    got = staged(rec)
    if got is None or not got[1]:
        return None
    return got[0] / got[1]
