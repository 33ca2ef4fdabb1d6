"""A cell, a configuration, a traffic mix and a per-layer metric added as
files alone: a throwaway benchmark root in a temporary directory is found
by name and run at a tiny size on the CPU through the harness, with the
harness's look for a chip bypassed."""

import json
import shutil
import time
from pathlib import Path

import pytest

from bench import harness

REPO = Path(__file__).resolve().parents[2]

TINY = {
    "name": "tiny", "source": "a tiny cube for the CPU", "source_num_slices": 8,
    "num_slices": 2, "lines_per_slice": 6, "points_per_line": 12,
    "observations": 64, "dtype": "float32",
    "types": ["normal", "uniform", "exponential", "lognormal"],
    "num_bins": 8, "window_lines": 3, "rep_bucket": 8, "group_tol": 1e-06,
    "set1_slices": [4, 5], "reduced": [],
    "tree": {"train_slices": [0, 1, 2, 3], "window_lines": 2, "depth": 2, "max_bins": 8},
}

METRIC = '''"""Windows the window handed back, a throwaway per-layer metric."""


def read(ctx):
    return float(len(ctx.window.handed_back))
'''


def make_root(tmp: Path, method: str, redundancy: str, limits: dict) -> Path:
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    (tmp / "bench" / "workloads").mkdir()
    (tmp / "bench" / "metrics").mkdir()
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (tmp / "bench" / "traffic" / "mix.json").write_text(
        json.dumps({"method": method, "redundancy": redundancy}))
    (tmp / "bench" / "workloads" / "tiny.mix.json").write_text(json.dumps(
        {"check": {"windows": 4, "points_per_window": 8, "limits": limits}}))
    (tmp / "bench" / "metrics" / "windows_handed_back.py").write_text(METRIC)
    shutil.copy(REPO / "bench" / "metrics" / "compiles_in_window.py",
                tmp / "bench" / "metrics")
    shutil.copy(REPO / "bench" / "peaks.json", tmp / "bench")
    (tmp / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.mix", "config": "tiny", "traffic": "mix", "chips": 1}],
        "end_to_end": [{"name": "points_per_s", "unit": "points/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "windows_handed_back", "unit": "count"},
                      {"name": "compiles_in_window", "unit": "count"}],
    }))
    return tmp


LOOSE = {"missing": 0, "unfitted": 0, "mean_gap": 1e-3, "std_gap": 1e-2,
         "params_gap": 5e-2, "error_gap": 5e-2}


def run(root: Path, trace: bool, capsys):
    cell = harness.load_cell("tiny.mix", root)
    result = harness.run_cell(cell, seed=2**31 + 7, seconds=0.2, trace=trace,
                              t_start=time.perf_counter(), run_dir=root / "run",
                              require_chip=False)
    out = capsys.readouterr()
    assert json.loads(out.out.strip().splitlines()[-1]) == json.loads(json.dumps(result))
    return result, out.err


@pytest.mark.parametrize("method,redundancy", [("grouping_ml", "dup"), ("baseline", "nodup")])
def test_new_cell_runs_from_files(tmp_path, capsys, method, redundancy):
    root = make_root(tmp_path, method, redundancy, LOOSE)
    result, err = run(root, trace=False, capsys=capsys)
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert set(result["metrics"]) == {"points_per_s", "setup_s"}
    assert result["metrics"]["points_per_s"]["value"] > 0
    assert list(result)[-1] == "check" and set(result["check"]) == set(LOOSE)
    last = err.strip().splitlines()[-len(LOOSE):]
    assert all(line.startswith("[check] ") and " limit=" in line for line in last)
    assert not (root / "run" / "calls").exists() and not (root / "run" / "cube").exists()


def test_new_metric_reads_from_its_file(tmp_path, capsys):
    root = make_root(tmp_path, "grouping_ml", "dup", LOOSE)
    result, _ = run(root, trace=True, capsys=capsys)
    assert result["metrics"]["windows_handed_back"]["value"] == result["attempted"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(result["device"])


def test_a_tight_limit_fails_the_run(tmp_path, capsys):
    root = make_root(tmp_path, "grouping_ml", "dup", dict(LOOSE, mean_gap=0.0))
    result, _ = run(root, trace=False, capsys=capsys)
    assert result["correct"] is False
