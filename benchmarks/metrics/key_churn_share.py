"""Host operators and native pane fold: the share of the window that the
thread which feeds the window engine (in ``nexmark_q5_live.sat`` the
pacing thread, see ``pacing_thread_busy_share``) spent on keys that come
and go: the engine's ``open`` (creating key states), ``trigger`` (finding
and queueing fired windows) and ``evict`` phases, timed by the engine
itself and entered under ``fold`` and ``flush``.  The largest such share
over the graph's threads; nothing where no thread has those phases."""
from benchmarks.harness import program_spans

PHASES = ("open", "trigger", "evict")


def read(rec):
    spans, g = program_spans.span_layer(), program_spans.graph_of(rec)
    if g is None:
        return None
    churn = {}
    for row in spans.triad(g, *program_spans.window_of(rec)):
        for p in PHASES:
            if p in row["phases"]:
                churn[row["track"]] = churn.get(row["track"], 0.0) \
                    + row["phases"][p]
    return max(churn.values()) / rec["window_s"] if churn else None
