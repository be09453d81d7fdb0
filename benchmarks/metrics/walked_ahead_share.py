"""Host operators and native pane fold: of the keys the window engine's
calls visited inside the window, the share visited in a call that ran
ahead of itself (``walked_ahead`` over ``key_touches`` of the program's
counter series): the engine fetches table records and key states before
it needs them where its table has outgrown the caches, and walks as
written where it has not.  1.0 on a table of tens of thousands of live
keys, 0 on one of a hundred.  Nothing on a program whose engine does not
count it, or where no key was visited."""
import os

from benchmarks.harness.runner import load_module

_touches = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "key_touches_per_event.py"),
    "benchmarks_metric_key_touches_per_event_for_walked_ahead")


def read(rec):
    got = _touches.touched(rec)
    if got is None or not got[0]:
        return None
    return got[1] / got[0]
