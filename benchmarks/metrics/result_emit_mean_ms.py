"""Result extraction and sink: mean ``t_emitted - t_on_host`` of the
launches in the window: ``_emit_results`` and the hand-off downstream
(the sink's function where the sink is fused behind the engine)."""
from benchmarks.harness import program_spans


def read(rec):
    return program_spans.launch_mean_ms(rec, "t_emitted", "t_on_host")
