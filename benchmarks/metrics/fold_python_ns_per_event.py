"""Host operators and native pane fold: what of ``fold_ns_per_event`` is
not the engine.  ``fold_ns_per_event`` less the share of it that the
engine's own clock says its batch calls took (``ingest_ns`` of the
program's counter series, ``windflow_tpu/telemetry/spans.Counters``: a
call from entry to return, less its ``open`` and ``trigger``, which are
child spans and not in the span's self time either), in nanoseconds an
event: the ctypes call, the conversions in front of it and the
operator's Python about it, cold on a thread that streams.  The share is
taken between the two notes of the series that bracket the window, of
the ``fold`` span between the same two instants
(``engine_clocks.of_fold``): this is a difference of a tenth of either
side, and a clock cut a bucket wider than its span was off by ten times
the widening.  Nothing on a program whose engine keeps no such clock,
nor on the Python store."""
import os

from benchmarks.harness.runner import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
_fold = load_module(os.path.join(_HERE, "fold_ns_per_event.py"),
                    "benchmarks_metric_fold_ns_per_event_for_fold_python")
_clocks = load_module(os.path.join(_HERE, "engine_clocks.py"),
                      "benchmarks_metric_engine_clocks_for_fold_python")


def read(rec):
    got = _clocks.of_fold(rec, ("ingest_ns",))
    return None if got is None or not got[0] else _fold.read(rec) - got[0]
