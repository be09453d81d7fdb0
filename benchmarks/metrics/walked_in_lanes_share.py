"""Host operators and native pane fold: of the tuples handed to the
window engine inside the window, the share walked in a call split over
the host lanes (``walked_in_lanes`` over ``folded_by_key +
folded_singly + inputs_ignored`` of the program's counter series, both
moved between the two notes that bracket the window,
``engine_clocks.moved``): the engine splits a call's tuple walk over a
few host threads where its table fits the caches and the call is long
enough (docs/RUNTIME.md 5b).  About 1.0 in a cell of a hundred keys and
chunks of 65,536, 0 where the table runs ahead of itself.  Nothing on a
program whose engine does not count it, or where no tuple was handed
over."""
import os

from benchmarks.harness.runner import load_module

_clocks = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "engine_clocks.py"),
    "benchmarks_metric_engine_clocks_for_walked_in_lanes")


def read(rec):
    got = _clocks.moved(rec, ("walked_in_lanes", "folded_by_key",
                              "folded_singly", "inputs_ignored"))
    if got is None or not sum(got[1:]):
        return None
    return got[0] / sum(got[1:])
