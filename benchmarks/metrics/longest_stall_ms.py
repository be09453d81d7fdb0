"""Source and ingest: the longest single wait span (``get_wait``,
``put_wait``, ``submit_wait``, ``ready_wait``) of any thread of the
graph that ended in the window."""
from benchmarks.harness import program_spans


def read(rec):
    return program_spans.longest_stall_ms(rec)
