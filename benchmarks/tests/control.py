#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python3 benchmarks/tests/control.py --workload <name> --seeds 1 2 3 [--events N]

For each seed: the reference of the workload's configuration at its own
sizes, computed once as the check computes it (float64 pane counts) and
once with the window's panes added in bfloat16, the second put in the
program's place.  Prints the numbers compared for both; the control has
to fail at least one.  Needs no chip: the control is the reference.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_pipeline(config):
    from benchmarks.harness.runner import load_module
    return load_module(os.path.join(ROOT, "benchmarks", "configs", config,
                                    "pipeline.py"),
                       f"benchmarks_pipeline_{config}")


def control_numbers(config, cfg, seed, n_events):
    """(the control's numbers, a sound run's numbers)."""
    import ml_dtypes

    from benchmarks.harness import check
    pipeline = load_pipeline(config)
    want = pipeline.reference(cfg, seed, n_events)
    low = pipeline.reference(cfg, seed, n_events, dtype=ml_dtypes.bfloat16)
    args = cfg["win_events"], cfg["slide_events"], {}
    numbers = [check.compare(got[:3], want, *args) for got in (low, want)]
    if hasattr(pipeline, "reference_fold"):
        # the sink's fold over the control's rows, as the sink would run it
        fold_want = pipeline.reference_fold(want)
        for n, got in zip(numbers, (low, want)):
            n.update(check.compare_folds(pipeline.reference_fold(got),
                                         fold_want))
    return tuple(numbers)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--events", type=int, default=200_000_000)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == args.workload)
    conf = next(c for c in manifest["configs"]
                if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    for seed in args.seeds:
        numbers, sound = control_numbers(cell["config"], cfg, seed,
                                         args.events)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "events": args.events, "control": numbers,
                          "sound": sound}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
