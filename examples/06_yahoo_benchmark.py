"""The Yahoo Streaming Benchmark model (the flagship application).

Ad events stream through filter (views only) -> static join
(ad -> campaign) -> per-campaign windowed counts on the device plane
(`models/yahoo.py`, BASELINE config #5).
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from examples._common import CountingSink, scale  # noqa: E402

import windflow_tpu as wf  # noqa: E402
from windflow_tpu.core import Mode  # noqa: E402
from windflow_tpu.models.yahoo import build_pipeline  # noqa: E402


def main():
    n = scale(1_000_000)
    sink = CountingSink()
    g = wf.PipeGraph("yahoo", Mode.DEFAULT)
    build_pipeline(g, n, batch_size=max(1024, n // 16),
                   device_batch=1024, sink=sink,
                   win_len=1 << 14, slide_len=1 << 14)
    g.run()
    print(f"[06] Yahoo benchmark: {n} ad events -> {sink.count} "
          f"per-campaign window counts, {sink.total:,.0f} views total")
    return sink


if __name__ == "__main__":
    main()
