"""Host operators and native pane fold: what the operators chained onto
the source (``ysb``: the numpy filter and the join; ``nexmark_q5_live``:
the law's map) cost an event.  The self time, inside the window, of the
``svc`` span a source's chain takes a chunk on the source's own thread
(``wf/<source>/svc``, beside its ``body``; the window engine's ``fold``
and ``flush`` are spans of their own and are not in it), over every
event the generator handed to the graph in the window, in nanoseconds:
per event *offered*, as ``fold_ns_per_event`` is.  The largest over the
graph's source threads; nothing where no source has a chain."""
from benchmarks.harness import program_spans


def read(rec):
    spans, g = program_spans.span_layer(), program_spans.graph_of(rec)
    if g is None or not rec["events"]:
        return None
    chain = [row["phases"]["svc"]
             for row in spans.triad(g, *program_spans.window_of(rec))
             if "body" in row["phases"] and "svc" in row["phases"]]
    return 1e9 * max(chain) / rec["events"] if chain else None
