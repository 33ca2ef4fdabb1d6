"""Every cell that ``BENCHMARK.json`` declares resolves through the
harness from the files in the repository: its configuration, traffic mix,
workload limits and per-layer metric readers. A configuration's file
names its source, its cuts and its reference, and the Set3 deployment is
among the configurations."""

import json
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_through_the_harness(name):
    cell = harness.load_cell(name, ROOT)
    assert cell.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                       if w["name"] == name)
    assert {"method", "redundancy"} <= set(cell.traffic)
    assert {"missing", "unfitted"} <= set(cell.workload["check"]["limits"])
    assert {m["name"] for m in cell.end_to_end} >= {"points_per_s", "setup_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.metric_reader(ROOT, m["name"]))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_names_its_source_cuts_and_reference(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == name and cfg["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert (ROOT / cfg["reference"]).is_file()
    assert any(w["config"] == name for w in BENCH["workloads"])


def test_set3_is_a_configuration_with_its_cell():
    assert "set3" in CONFIGS and "set3.grouping_ml" in CELLS
    cfg = harness.load_cell("set3.grouping_ml", ROOT).config
    assert (cfg["observations"], cfg["points_per_line"], cfg["window_lines"]) == (
        10_000, 251, 25)
    assert cfg["reduced"] == ["num_slices", "lines_per_slice"]
