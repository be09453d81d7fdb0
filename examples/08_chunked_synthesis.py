"""The headline lane: fused synthesis + ingest via SynthChunk.

A declared SyntheticSource with ``chunked=True`` ships tiny SynthChunk
descriptors instead of materialized columns; the device window stage's
C++ engine generates and folds each chunk in one pass (no host arrays
at all -- the columnar twin of the record plane's set_synth lowering).
Everything else in the graph is unchanged, and any non-chunk-aware
consumer transparently receives materialized batches.

This is the synthesis lane of the benchmark's headline shape (its
rate is not measured on the current machine; see PERF.md).
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from examples._common import CountingSink, scale  # noqa: E402

import windflow_tpu as wf  # noqa: E402
from windflow_tpu.core import Mode  # noqa: E402
from windflow_tpu.operators.basic_ops import Sink  # noqa: E402
from windflow_tpu.operators.synth import SyntheticSource  # noqa: E402

WIN, SLIDE, N_KEYS = 4096, 2048, 64


def run(n, chunked):
    sink = CountingSink()
    op = wf.WinSeqTPUBuilder("sum").withTBWindows(WIN, SLIDE) \
        .withBatch(4096).withBatchOutput().withInflight(8).build()
    g = wf.PipeGraph("chunked" if chunked else "materialized",
                     Mode.DEFAULT)
    g.add_source(SyntheticSource(n, N_KEYS, batch=1 << 20,
                                 chunked=chunked)) \
        .add(op).add_sink(Sink(sink))
    t0 = time.perf_counter()
    g.run()
    return time.perf_counter() - t0, sink


def main():
    n = scale(16_000_000)
    run(max(1000, n // 10), chunked=False)  # warm-up: backend init +
    #                                         XLA compile must not bias
    #                                         the first timed run
    dt_mat, s_mat = run(n, chunked=False)
    dt_chk, s_chk = run(n, chunked=True)
    assert s_chk.count == s_mat.count and s_chk.total == s_mat.total, \
        "the two feeds must compute identical windows"
    print(f"[08] {n:,} tuples, {s_chk.count} windows -- materialized "
          f"feed {n / dt_mat / 1e6:.1f}M tuples/s, chunked synthesis "
          f"{n / dt_chk / 1e6:.1f}M tuples/s (identical results)")
    return s_chk


if __name__ == "__main__":
    main()
