"""Host operators and native pane fold: for the pacing thread (see
``pacing_thread_busy_share``), the share of the window its spans account
for: ``get_wait`` + ``svc`` + ``put_wait`` + children, ``body``
included.  What is missing from 1 is host time under no span."""
from benchmarks.harness import program_spans


def read(rec):
    found = program_spans.pacing_thread(rec)
    return None if found is None else found[2]
