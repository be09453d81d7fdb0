"""Dispatch: the bytes the launches shipped to the device inside the
window (``bytes_in`` of the program's launch records,
``windflow_tpu/telemetry/spans.LaunchRing``: the staged columns and the
extents of every launch whose result reached the host in the window)
over the events handed to the graph in it.  0.2 where a firing ships a
key's two panes a slide of half a million events; a kilobyte where every
launch ships 3,630 panes a key again.  Nothing on a program without
launch records."""
from benchmarks.harness import program_spans


def read(rec):
    recs = program_spans._launches(rec)
    if not recs or not rec.get("events"):
        return None
    return sum(r.bytes_in for r in recs) / rec["events"]
