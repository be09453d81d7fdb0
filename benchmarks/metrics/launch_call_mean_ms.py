"""Dispatch: mean ``t_called - t_packed`` of the launches whose result
reached the host in the window: the jitted call with the packed host
buffer, from Python's way into it to its return (the runtime's transfer
set-up and the enqueue), the second part of ``launch_dispatch_mean_ms``;
what is left of that after this and ``launch_pack_mean_ms`` is the
handle starting the copy back and the way out of ``engine.compute``.
Nothing on a program whose launch records carry no such stamps.  In the
two open-loop cells what it moves is ``result_latency_p50_ms``, not the
``events_per_s`` the manifest names (``launch_pack_mean_ms``)."""
import os

from benchmarks.harness.runner import load_module

_pack = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "launch_pack_mean_ms.py"),
    "benchmarks_metric_launch_pack_for_launch_call")


def read(rec):
    return _pack.part_mean_ms(rec, "t_called", "t_packed")
