"""Host operators and native pane fold: what the native ingest costs an
event.  The self time, inside the window, of the ``fold`` span on the
thread that feeds the window engine (the whole native ingest of a chunk
and the operator's Python about it; not its ``flush`` child, nor the
engine's own ``open`` and ``trigger``, which are spans of their own),
over every event the generator handed to the graph in the window, in
nanoseconds.  Where operators before the window engine drop events
(``ysb``: two in three die in the filter) it is still per event offered.
In a closed loop ``pacing_thread_busy_share`` stays near 0.9 however
fast the fold is; this reading moves.  The largest over the graph's
threads; nothing where no thread has a ``fold``."""
from benchmarks.harness import program_spans


def read(rec):
    spans, g = program_spans.span_layer(), program_spans.graph_of(rec)
    if g is None or not rec["events"]:
        return None
    fold = {}
    for row in spans.triad(g, *program_spans.window_of(rec)):
        if "fold" in row["phases"]:
            fold[row["track"]] = fold.get(row["track"], 0.0) \
                + row["phases"]["fold"]
    return 1e9 * max(fold.values()) / rec["events"] if fold else None
