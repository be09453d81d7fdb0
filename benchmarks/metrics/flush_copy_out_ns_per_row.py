"""Host operators and native pane fold: what copying a flush's columns
out of the engine costs a result row.  ``copy_out_ns`` of the program's
counter series (``perf_counter_ns`` round the copies in
``NativeWindowEngine.flush``: the pane partials into pooled float64
buffers, the five row columns) over ``windows_staged`` of the same
series (a staged window is a result row), both moved between the same
two notes that bracket the window (``engine_clocks.ratio``), in
nanoseconds: what a flush that staged straight into the launch's buffers
would not pay.  Nothing on a program that keeps no such clock, or where
nothing was staged."""
import os

from benchmarks.harness.runner import load_module

_clocks = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "engine_clocks.py"),
    "benchmarks_metric_engine_clocks_for_flush_copy_out")


def read(rec):
    return _clocks.ratio(rec, "copy_out_ns", "windows_staged")
