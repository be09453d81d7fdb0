"""jax.profiler capture hook around device launches
(docs/OBSERVABILITY.md).

``launch_span(label)`` wraps every window-engine program launch (the
dispatcher thread's ``engine.compute`` call).  By default it is a
no-op null context; setting ``WINDFLOW_JAX_PROFILE=1`` turns it into a
``jax.profiler.TraceAnnotation``, so a profiler capture started with
``jax.profiler.start_trace(logdir)`` (or the live
``start_server``/TensorBoard flow) shows each launch as a named span
that lines up with the per-launch ``Device_time_ms`` wall numbers in
the stats JSON.

Resolution happens once per process, on first use, never at import --
the telemetry plane must not pull jax into processes that only run the
host plane.
"""
from __future__ import annotations

import os
from contextlib import nullcontext

_impl = None  # resolved on first launch_span call


def _resolve():
    if os.environ.get("WINDFLOW_JAX_PROFILE", "0") == "0":
        return lambda label: nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def launch_span(label: str):
    """Context manager spanning one device launch."""
    global _impl
    if _impl is None:
        _impl = _resolve()
    return _impl(label)


def reset() -> None:
    """Re-read WINDFLOW_JAX_PROFILE (tests)."""
    global _impl
    _impl = None
