"""CPU tests of the benchmark: ``python3 -m pytest benchmarks/tests``.

Held to the CPU backend with four virtual devices (the mesh
configuration needs them); XLA_FLAGS must be set before the backend
starts.
"""
import json
import os
import shutil
import sys

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# sizes a CPU test can hold; the shapes' ratios are the cells' own
TINY_CONFIG = {
    "nexmark_q5": {"win_events": 4096, "slide_events": 2048,
                   "pool_rows": 1 << 15},
    "nexmark_q5_mesh4": {"win_events": 4096, "slide_events": 2048,
                         "pool_rows": 1 << 15},
    "ysb": {"win_events": 8192, "slide_events": 8192,
            "pool_rows": 1 << 15},
}
TINY_TRAFFIC = {
    "sat": {"chunk_events": 1024, "warmup_s": 0.3,
            "warmup_min_result_batches": 2},
    "paced": {"chunk_events": 1000, "rate_events_per_s": 200_000,
              "warmup_s": 0.3, "warmup_min_result_batches": 2,
              "settle_s": 0.2, "settle_max_s": 2.0},
}


def _patch(path, changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def with_parked(manifest):
    """The manifest with the cells that are parked beside their
    configuration (``configs/<name>/parked.json``: proven correct, not
    admitted yet) added, so that their files stay tested."""
    manifest = json.loads(json.dumps(manifest))
    configs = os.path.join(ROOT, "benchmarks", "configs")
    for name in sorted(os.listdir(configs)):
        path = os.path.join(configs, name, "parked.json")
        if os.path.isfile(path) and name not in [
                c["name"] for c in manifest["configs"]]:
            with open(path) as f:
                parked = json.load(f)
            manifest["configs"].append(parked["config"])
            manifest["workloads"].append(parked["workload"])
    return manifest


@pytest.fixture
def tiny_bench(tmp_path, manifest):
    """A copy of ``benchmarks/`` and the manifest with every
    configuration and mix cut to a size a CPU test can hold; returns
    (manifest, path of the copied ``benchmarks``)."""
    dst = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), dst,
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "tests"))
    for name, changes in TINY_CONFIG.items():
        _patch(dst / "configs" / name / "config.json", changes)
    for name, changes in TINY_TRAFFIC.items():
        _patch(dst / "traffic" / (name + ".json"), changes)
    manifest = with_parked(manifest)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    return manifest, str(dst)
