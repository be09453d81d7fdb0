#!/usr/bin/env python3
"""The control of ``smartgrid_median`` on the chip.

    python3 benchmarks/tests/control_median.py --seed <n> [--seconds 20]

The cell run as the benchmark runs it, with one change to the program:
the loads reach the user's median in bfloat16, the nearest precision
below the float32 the configuration states (each launch's columns cast
on their way to the device).  Above 256 W bfloat16's spacing is 2 W and
more (16 W at 2,048), so most windows' medians come back off by a few
watts: the comparison has to say not correct.  Prints the run's result
line.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def through_bfloat16(graph, gen, sink):
    """Every launch of the graph's window operator hands the user's
    function its loads rounded to bfloat16."""
    import ml_dtypes
    import numpy as np

    from windflow_tpu.graph.fuse import iter_logics
    logic = next(lg for _, lg in iter_logics(graph)
                 if hasattr(lg, "launched_batches"))
    compute = logic.engine.compute

    def low(cols, starts, ends, gwids):
        cols = {k: np.asarray(v).astype(ml_dtypes.bfloat16)
                .astype(np.float64) for k, v in cols.items()}
        return compute(cols, starts, ends, gwids)

    logic.engine.compute = low


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from benchmarks.harness import runner
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    result = runner.run_cell(manifest, "smartgrid_median.sat", args.seed,
                             args.seconds, False,
                             runner.Doors(tamper=through_bfloat16))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
