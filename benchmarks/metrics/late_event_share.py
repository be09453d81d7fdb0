"""Host operators and native pane fold: of the events handed to the graph
inside the window, the share the window engine accepted with a stamp
behind its stream time when they came (``late_accepted`` of the program's
counter series, ``windflow_tpu/telemetry/spans.Counters``, cut at the
window to a note and a 100 ms bucket at each end).  By the law of
``nexmark_q5_ooo`` one bid in ten.  Nothing on a program whose engine
does not count it."""
from benchmarks.harness import program_spans


def moved(rec):
    """By how much each counter of the series moved inside the window,
    summed over the graph's window operators; None where the program
    keeps no such series."""
    g = program_spans.graph_of(rec)
    total = None
    for c in list((getattr(g, "counters", None) or {}).values()):
        if not hasattr(c, "moved_between"):
            return None
        by = c.moved_between(*program_spans.window_of(rec))
        total = by if total is None else {
            n: total[n] + v for n, v in by.items()}
    return total


def read(rec):
    by = moved(rec)
    if by is None or "late_accepted" not in by or not rec.get("events"):
        return None
    return by["late_accepted"] / rec["events"]
