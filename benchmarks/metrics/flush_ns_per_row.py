"""Host operators and native pane fold: what staging costs a result row.
The self time, inside the window, of the ``flush`` span on the thread
that feeds the window engine (the engine's copy of the fired windows'
pane spans into a launch's buffers, the copy of those buffers out of the
engine, and the operator's Python about it; not its ``submit_wait`` or
``evict`` children) over the result rows the sink received in the window,
in nanoseconds.  The largest over the graph's threads; nothing where no
thread has a ``flush`` or no row came."""
from benchmarks.harness import program_spans


def read(rec):
    spans, g = program_spans.span_layer(), program_spans.graph_of(rec)
    if g is None or not rec.get("rows"):
        return None
    flush = {}
    for row in spans.triad(g, *program_spans.window_of(rec)):
        if "flush" in row["phases"]:
            flush[row["track"]] = flush.get(row["track"], 0.0) \
                + row["phases"]["flush"]
    return 1e9 * max(flush.values()) / rec["rows"] if flush else None
