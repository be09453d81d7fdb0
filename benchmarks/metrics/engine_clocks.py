"""What the readers of the inside of ``fold`` and ``flush`` share (no
metric of its own): the native engine's clocks and counts of the
program's counter series (``windflow_tpu/telemetry/spans.ENGINE_CLOCKS``)
cut at the run's window, **with what they are set against cut where they
are**.  The series keeps the last note of each 100 ms bucket, so what
moved "in the window" moved between the last note before the bucket of
the window's opening and the last in the bucket of its close: a note and
a bucket wider at each end, 1 % of a window of 20 s.  A part cut that way
over a whole, or a count, cut at the window's instants reads that much
high, and a difference of two such (``fold_python_ns_per_event``) is off
by its multiple.  So a reader here divides by what the *same two notes*
bracket: a count of the series itself (:func:`ratio`), or the ``fold``
span cut at the notes' instants (:func:`of_fold`).  Nothing on a
program whose engine keeps no such clock (a parent commit, the Python
store): every function gives None and nothing raises."""
import os

from benchmarks.harness import program_spans
from benchmarks.harness.runner import load_module

_fold = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "fold_ns_per_event.py"),
    "benchmarks_metric_fold_ns_per_event_for_engine_clocks")


def _kept(rec, names, reader):
    """The graph's window operators' counters, where every one of them
    has ``reader`` and counts every one of ``names``; else None."""
    g = program_spans.graph_of(rec)
    kept = list((getattr(g, "counters", None) or {}).values())
    if not kept or any(not hasattr(c, reader)
                       or any(n not in c.values for n in names)
                       for c in kept):
        return None
    return kept


def moved(rec, names):
    """By how much each of ``names`` moved between the two notes that
    bracket the window, summed over the graph's window operators, in
    ``names``' order: one cut for all of them, so a ratio of two is of
    the same stretch of the run."""
    kept = _kept(rec, names, "between")
    if kept is None:
        return None
    t0, t1 = program_spans.window_of(rec)
    return tuple(sum(col) for col in zip(*(c.between(names, t0, t1)
                                           for c in kept)))


def ratio(rec, name, over):
    """``name`` over the count ``over`` of the same series, both moved
    between the same two notes; None where either did not move."""
    got = moved(rec, (name, over))
    return got[0] / got[1] if got and got[0] and got[1] else None


def at_the_notes(rec, names):
    """``(moved, rec_at)``: :func:`moved`, and the run's record cut at
    the instants of those two notes in the place of the window's (the
    earliest and the latest over the operators).  A reader of the span
    timelines handed ``rec_at`` reads the whole that the clocks' parts
    are parts of; its ``events`` and ``rows`` are the window's still (a
    feed in bursts does not offer at one rate, so they cannot be scaled
    to the cut): use ``rec_at`` for a ratio of two of its readings."""
    kept = _kept(rec, names, "cut")
    if kept is None:
        return None
    t0, t1 = program_spans.window_of(rec)
    cuts = [c.cut(t0, t1) for c in kept]
    if None in cuts:
        return None
    lo, hi = min(a for a, _ in cuts), max(b for _, b in cuts)
    return moved(rec, names), dict(rec, _window_of=(lo, hi),
                                   window_s=hi - lo)


def of_fold(rec, names):
    """What each of ``names`` is of ``fold_ns_per_event``, in nanoseconds
    an event: the clock moved between the two notes, over the ``fold``
    span's self time between the same two instants, times the accepted
    reading of the span at the window.  The share is of one cut and asks
    nothing of the rate events come at; the parts of a whole add up to
    no more than ``fold_ns_per_event`` itself.  None where the span or a
    clock has nothing to read."""
    got = at_the_notes(rec, names)
    if got is None:
        return None
    clocks, rec_at = got
    whole, whole_at = _fold.read(rec), _fold.read(rec_at)
    if not whole or not whole_at:
        return None
    span_ns = whole_at * rec_at["events"]
    return tuple(whole * ns / span_ns for ns in clocks)
