"""Host operators and native pane fold: what the engine's own part of a
flush costs a result row.  ``stage_ns`` of the program's counter series
(the engine's clock round ``Engine::flush`` from entry to return, less
its ``evict``: staging the fired windows' pane spans into its buffers,
retiring the rings, the row loop) over ``windows_staged`` of the same
series, the windows those flushes staged (a staged window is a result
row), both moved between the same two notes that bracket the window
(``engine_clocks.ratio``), in nanoseconds.  With
``flush_copy_out_ns_per_row`` it is the part of ``flush_ns_per_row`` that
is not the operator's Python.  Nothing on a program whose engine keeps no
such clock, or where nothing was staged."""
import os

from benchmarks.harness.runner import load_module

_clocks = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "engine_clocks.py"),
    "benchmarks_metric_engine_clocks_for_flush_stage")


def read(rec):
    return _clocks.ratio(rec, "stage_ns", "windows_staged")
