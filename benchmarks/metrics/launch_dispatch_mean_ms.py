"""Dispatch: mean ``t_dispatched - t_picked`` of the launches whose
result reached the host in the window: the host's time inside
``engine.compute`` (staging the operands, enqueueing the program)."""
from benchmarks.harness import program_spans


def read(rec):
    return program_spans.launch_mean_ms(rec, "t_dispatched", "t_picked")
