"""NEXmark Q5 on a four-chip mesh: the same stream, data and reference as
``nexmark_q5``, with ``KeyFarmMesh`` in place of ``KeyFarmTPU``."""
import os

from benchmarks.harness.runner import load_module

_q5 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "nexmark_q5", "pipeline.py"),
                  "benchmarks_pipeline_nexmark_q5_for_mesh4")

make_pool = _q5.make_pool
reference, reference_fold = _q5.reference, _q5.reference_fold
SinkFold = _q5.SinkFold
logical_bytes_per_row = _q5.logical_bytes_per_row


def build(graph, cfg, source_body, sink, seed):
    import windflow_tpu as wf
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchSource
    from windflow_tpu.operators.tpu.mesh_farm import KeyFarmMesh
    from windflow_tpu.parallel.mesh import make_mesh
    counter = KeyFarmMesh(make_mesh(cfg["chips"], win_axis=1),
                          cfg["win_events"], cfg["slide_events"],
                          wf.WinType.TB,
                          batch_windows=cfg["batch_windows"],
                          name="q5_counts_mesh", kind="count")
    graph.add_source(BatchSource(source_body)).add(counter) \
        .add_sink(Sink(sink, name="q5_sink"))


def launches(graph):
    """Mesh launches so far: the logic's own ``launched_batches`` (the
    stats records do not carry them)."""
    from windflow_tpu.graph.fuse import iter_logics
    return sum(getattr(lg, "launched_batches", 0)
               for _, lg in iter_logics(graph))
