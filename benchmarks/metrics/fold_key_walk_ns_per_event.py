"""Host operators and native pane fold: what the engine's walks that
cost by the key take an event.  The share of ``fold_ns_per_event`` that
``key_walk_ns`` of the program's counter series is (the engine's own
clock round the visit by key of every batch call: ``prepare`` of the
keys the call did not open, and ``settle``), in nanoseconds an event,
cut as ``fold_tuple_walk_ns_per_event`` is (``engine_clocks.of_fold``).
0.03 where a call of 65,536 tuples meets 111 keys; what a cell of many
keys pays a key is this over ``key_touches_per_event``.  Nothing on a
program whose engine keeps no such clock."""
import os

from benchmarks.harness.runner import load_module

_clocks = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "engine_clocks.py"),
    "benchmarks_metric_engine_clocks_for_fold_key_walk")


def read(rec):
    got = _clocks.of_fold(rec, ("key_walk_ns",))
    return got[0] if got and got[0] else None
