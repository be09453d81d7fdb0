"""Host operators and native pane fold: what the engine's walks that
cost by the tuple take an event.  The share of ``fold_ns_per_event``
that ``tuple_walk_ns`` of the program's counter series is (the engine's
own clock round ``gather``, ``note_late`` and ``fold_singly`` in every
batch call), in nanoseconds an event: the clock moved between the two
notes of the series that bracket the window, over the ``fold`` span
between the same two instants, times the span's reading at the window
(``engine_clocks.of_fold``).  With ``fold_python_ns_per_event`` and
``fold_key_walk_ns_per_event`` it is ``fold_ns_per_event``, but for the
call's way in and out.  Nothing on a program whose engine keeps no such
clock."""
import os

from benchmarks.harness.runner import load_module

_clocks = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "engine_clocks.py"),
    "benchmarks_metric_engine_clocks_for_fold_tuple_walk")


def read(rec):
    got = _clocks.of_fold(rec, ("tuple_walk_ns",))
    return got[0] if got and got[0] else None
