"""Device-batched window aggregation: WinSeqTPU on the columnar plane.

The columnar fast path: a BatchSource produces TupleBatches (struct of
numpy arrays), WinSeqTPU folds them into per-key pane accumulators at
ingest and launches batched window reductions on the device (the
Win_Seq_GPU re-design -- win_seq_gpu.hpp:391-645 -- as XLA programs).
Under ``JAX_PLATFORMS=cpu`` the same graph runs on XLA's CPU backend
unchanged.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from examples._common import CountingSink, scale  # noqa: E402

import numpy as np  # noqa: E402

import windflow_tpu as wf  # noqa: E402
from windflow_tpu.core import Mode  # noqa: E402
from windflow_tpu.core.tuples import TupleBatch  # noqa: E402
from windflow_tpu.operators.basic_ops import Sink  # noqa: E402
from windflow_tpu.operators.batch_ops import BatchSource  # noqa: E402

WIN, SLIDE = 512, 256


def main():
    n, n_keys, batch = scale(2_000_000), 16, 16_384
    state = {"sent": 0}
    arange = np.arange(batch, dtype=np.int64)

    def source(ctx):
        i = state["sent"]
        if i >= n:
            return None
        m = min(batch, n - i)
        ids = (arange[:m] + i) // n_keys
        state["sent"] = i + m
        return TupleBatch({"key": (arange[:m] + i) % n_keys, "id": ids,
                           "ts": ids, "value": np.ones(m, np.float32)})

    sink = CountingSink()
    op = wf.WinSeqTPUBuilder("sum").withTBWindows(WIN, SLIDE) \
        .withBatch(1024).withBatchOutput().build()
    g = wf.PipeGraph("device", Mode.DEFAULT)
    g.add_source(BatchSource(source)).add(op).add_sink(Sink(sink))
    g.run()
    # every full window sums WIN ones
    full = sink.count * WIN
    print(f"[03] {n} tuples -> {sink.count} device-computed windows, "
          f"sum {sink.total:,.0f} (<= {full:,} = count*win; EOS windows "
          f"are partial)")
    return sink


if __name__ == "__main__":
    main()
