#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` in a new process.  It refuses
any backend that is not ``tpu`` (exit code 2, no result line), builds the
graph, warms up, measures for ``--seconds``, checks what the sink
received against the plain reference, and prints one JSON object as the
last line of standard output.  See ``benchmarks/README.md``.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks.harness import runner
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    try:
        result = runner.run_cell(manifest, args.workload, args.seed,
                                 args.seconds, bool(args.trace))
    except runner.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
