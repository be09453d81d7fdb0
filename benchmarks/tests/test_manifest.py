"""A lint of ``BENCHMARK.json`` against the driver's contract, as far as
a test can read it."""
import os
import re

import pytest

from benchmarks.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(manifest):
    assert set(manifest) == KEYS
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert all(line(w) for w in manifest["command"])
    assert all(PATH.match(p) and os.path.isdir(os.path.join(ROOT, p))
               for p in manifest["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    # a full check of 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert os.path.isfile(os.path.join(
            ROOT, os.path.dirname(c["file"]), "pipeline.py"))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_config_files_state_what_the_manifest_says(manifest):
    import json
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert all(k in data for k in c["reduced"])
        assert set(data["guarantees"]) == {"delivery", "completeness",
                                           "exactness"}


def test_workloads(manifest):
    names = [w["name"] for w in manifest["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    four = 0
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
        four += w["chips"] == 4
    assert four <= max(1, len(names) // 2)


def cells_of(metric, manifest):
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


def test_end_to_end(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and 0 < e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(cells_of(m, manifest)) <= cells
    for cell in cells:
        mine = [m["name"] for m in manifest["end_to_end"]
                if cell in cells_of(m, manifest)]
        assert "setup_s" in mine and len(mine) >= 2


def test_per_layer(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = [m["name"] for m in manifest["per_layer"]] + list(e2e)
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}
    covered = set()
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in e2e
        mine = set(cells_of(m, manifest))
        assert mine <= cells
        # every cell that reads it reports the metric it should move
        assert mine <= set(cells_of(e2e[m["moves"]], manifest))
        if "workloads" not in m:
            assert set(cells_of(e2e[m["moves"]], manifest)) == cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"] + ".py"))
        covered |= mine
    assert covered == cells


def test_one_layer_one_spelling(manifest):
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert len({layer.lower() for layer in layers}) == len(layers)


def test_files_are_named_from_a_names_characters(manifest):
    for p in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x not in ("__pycache__", "out")]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel


def test_peaks_name_their_source():
    import json
    with open(os.path.join(ROOT, "benchmarks", "harness",
                           "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["hbm_gb_per_s"] == {"TPU v5 lite": 819.0}
    assert "source" in peaks
    with pytest.raises(KeyError):
        peaks["hbm_gb_per_s"]["cpu"]


def test_a_parked_cell_would_pass_the_same_lint(manifest):
    """``configs/<name>/parked.json`` holds the entries of a cell that is
    proven correct and not admitted yet: added to the manifest they meet
    every rule above."""
    from benchmarks.tests.conftest import with_parked
    wide = with_parked(manifest)
    assert len(wide["workloads"]) > len(manifest["workloads"])
    for rule in (test_configs, test_config_files_state_what_the_manifest_says,
                 test_workloads, test_end_to_end, test_per_layer):
        rule(wide)
