"""Dispatch: the host arrays a launch handed to the device, mean over the
launches whose result reached the host in the window (``buffers_in`` of
the program's launch records, ``windflow_tpu/telemetry/spans.Launch``).
1.0 where the engine packs a launch's value columns and extents into one
buffer and calls the jitted program with it; every array more is a
transfer of its own and, before PR 36, a ``jnp.asarray`` on the
dispatcher's thread.  Nothing on a program whose launch records do not
count them."""
from benchmarks.harness import program_spans


def read(rec):
    recs = program_spans._launches(rec)
    if not recs or not all(hasattr(r, "buffers_in") for r in recs):
        return None
    return sum(r.buffers_in for r in recs) / len(recs)
