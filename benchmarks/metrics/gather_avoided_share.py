"""Host operators and native pane fold: of the columns that the selected
batches reaching the window operator carried (a filtered ``TupleBatch``
holds its base columns and the surviving rows), the share nobody had to
gather: ``1 - cols_gathered / cols_selected`` of the program's counters
(``windflow_tpu/telemetry/spans.Counters``), since the graph started:
plain integer adds with no clock, so not cut at the window; warm-up is
the same traffic.  0.8 where a join reads one column of five and the
engine reads the rest through the selection; nothing where no batch
carried a selection, or on a program that does not count it."""
from benchmarks.harness import program_spans


def read(rec):
    g = program_spans.graph_of(rec)
    carried = gathered = 0
    for c in list((getattr(g, "counters", None) or {}).values()):
        if not hasattr(c, "cols_selected"):
            return None
        carried += c.cols_selected
        gathered += c.cols_gathered
    return 1.0 - gathered / carried if carried else None
