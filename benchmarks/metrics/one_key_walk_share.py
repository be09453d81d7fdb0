"""Host operators and native pane fold: of the tuples handed to the
window engine inside the window, the share its one-key walk took
(``walked_one_key`` over ``folded_by_key + folded_singly +
inputs_ignored`` of the program's counter series, both moved between the
two notes that bracket the window, ``engine_clocks.moved``): a call whose
tuples all carry one key, after a call that touched one key, on a lane
that folds by key, is walked once with no probe (docs/RUNTIME.md 5b).
About 1.0 in a cell with one global key, 0 where a call holds many keys.
Nothing on a program whose engine does not count it, or where no tuple
was handed over."""
import os

from benchmarks.harness.runner import load_module

_clocks = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "engine_clocks.py"),
    "benchmarks_metric_engine_clocks_for_one_key_walk")


def read(rec):
    got = _clocks.moved(rec, ("walked_one_key", "folded_by_key",
                              "folded_singly", "inputs_ignored"))
    if got is None or not sum(got[1:]):
        return None
    return got[0] / sum(got[1:])
