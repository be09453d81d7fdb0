"""Host operators and native pane fold: the largest, over the graph's
operator threads, of the share of the window the thread was busy: self
time of ``svc`` and its working children (``keyby``, ``fold``, ``flush``,
``stage``, ``dispatch``, ``emit``), never a wait, never a source's
``body`` (the load generator).  Which thread it is:
``program_spans.pacing_thread(rec)[0]`` (its name is the fused chain's
operators joined with ``+``)."""
from benchmarks.harness import program_spans


def read(rec):
    found = program_spans.pacing_thread(rec)
    return None if found is None else found[1]
