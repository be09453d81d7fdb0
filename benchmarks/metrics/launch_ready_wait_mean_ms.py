"""Dispatch: mean ``t_on_host - t_dispatched`` of the launches in the
window: the device, the copy back, and however long the dispatcher took
to look.  With ``launch_dispatch_mean_ms`` it is what
``launch_roundtrip_mean_ms`` sums."""
from benchmarks.harness import program_spans


def read(rec):
    return program_spans.launch_mean_ms(rec, "t_on_host", "t_dispatched")
