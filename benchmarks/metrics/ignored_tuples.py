"""Host operators and native pane fold: the tuples the window engine had
dropped by the window's end, behind a window that had fired
(``inputs_ignored`` of the program's counters, since the graph started:
what was dropped before the window opened is as wrong as what was
dropped in it).  0, or the run is not ``correct`` anyway: the reading
says where to look.  Nothing on a program whose engine does not put it
among its counters."""
from benchmarks.harness import program_spans


def read(rec):
    g = program_spans.graph_of(rec)
    total = None
    for c in list((getattr(g, "counters", None) or {}).values()):
        if "inputs_ignored" not in getattr(c, "values", {}):
            return None
        total = (total or 0) + c.values["inputs_ignored"]
    return total
