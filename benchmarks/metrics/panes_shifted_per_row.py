"""Host operators and native pane fold: the ring elements the engine's
``retire()`` moved down after a staging, over the windows staged
(``panes_shifted`` / ``windows_staged`` of the program's counter series,
both moved between the same two notes that bracket the window,
``engine_clocks.ratio``).  Set beside ``panes_staged_per_row`` it says
what of ``flush_stage_ns_per_row`` is the ring's shift and what the
staging: 134 against 118 where a window is 3,600 panes (16 bytes an
element either way), so a ring that did not shift would save about half
the engine's part of a flush there; 1.5 against 2 where a window is two
panes.  Nothing on a program whose engine does not count it, or where
nothing was shifted."""
import os

from benchmarks.harness.runner import load_module

_clocks = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "engine_clocks.py"),
    "benchmarks_metric_engine_clocks_for_panes_shifted")


def read(rec):
    return _clocks.ratio(rec, "panes_shifted", "windows_staged")
