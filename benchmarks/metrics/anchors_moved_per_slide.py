"""Host operators and native pane fold: the times a live key's anchor
moved back inside the window (``anchors_moved`` of the program's counter
series: a tuple of the key arrived that lies before the window the key
would fire next, docs/RUNTIME.md 5a) over the slides of stream the window
took in, which is its firings: events handed to the graph in it over
``slide_events``.  Nothing on a program whose engine does not count it."""
import os

from benchmarks.harness.runner import load_module

_late = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "late_event_share.py"),
                    "benchmarks_metric_late_event_share_for_anchors")


def read(rec):
    by = _late.moved(rec)
    if by is None or "anchors_moved" not in by or not rec.get("events"):
        return None
    return by["anchors_moved"] * rec["config"]["slide_events"] \
        / rec["events"]
