"""Host operators and native pane fold: the most key states the window
engine held at once inside the window (``keys_live`` of the program's
counter series, ``windflow_tpu/telemetry/spans.Counters``; where no key
came or went in the window, the count it stood at).  Bounded by the live
population where the engine forgets dead keys; growing with the run
where it does not."""
from benchmarks.harness import program_spans


def read(rec):
    g = program_spans.graph_of(rec)
    kept = getattr(g, "counters", None)
    if not kept:
        return None
    t0, t1 = program_spans.window_of(rec)
    peaks = [c.live_peak(t0, t1) for c in list(kept.values())]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
