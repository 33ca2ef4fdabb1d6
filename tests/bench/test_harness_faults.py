"""Each cell's check catches a broken timed path.

A run of each cell at a tiny size on the CPU (the look for a chip
bypassed, the cell's own types, bins, method, redundancy and limits) must
come out correct as it is, and not correct with each fault this pipeline
can have planted under the timed path:

* ``unchanged``: the compute step returns its state unchanged (zeros);
* ``half``: the moments taken over half of each point's observations;
* ``altered``: an answer altered where it is produced (the type of every
  third point moved to the next candidate, its parameters and error kept).

The exchange between chips does not exist in a one-chip cell.
"""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness

from repro.core import executor as ex_mod

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
TINY = {"source_num_slices": 501, "num_slices": 2, "lines_per_slice": 4,
        "points_per_line": 10, "window_lines": 2, "rep_bucket": 8,
        "tree": {"train_slices": [0, 1, 2, 3], "window_lines": 2, "depth": 4,
                 "max_bins": 32}}


def tiny_cell(name):
    cell = harness.load_cell(name, ROOT)
    cell.config = dict(cell.config, **TINY)
    cell.workload = {"check": dict(cell.workload["check"], windows=4, points_per_window=8)}
    return cell


def plant(monkeypatch, fault):
    if fault == "half":
        orig = ex_mod._jitted_fns

        def half(*a):
            moments_f, *rest = orig(*a)
            return (lambda v: moments_f(v[:, : v.shape[1] // 2]), *rest)

        monkeypatch.setattr(ex_mod, "_jitted_fns", half)
        return
    orig_sf = ex_mod.StagedExecutor._select_and_fit

    def broken(self, values, moments, window, **kw):
        t, p, e, fitted, hits = orig_sf(self, values, moments, window, **kw)
        if fault == "unchanged":
            t, p, e = np.zeros_like(t), np.zeros_like(p), np.zeros_like(e)
        else:
            t = t.copy()
            t[::3] = (t[::3] + 1) % len(self.config.types)
        return t, p, e, fitted, hits

    monkeypatch.setattr(ex_mod.StagedExecutor, "_select_and_fit", broken)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [None, "unchanged", "half", "altered"])
def test_check_catches_each_fault(tmp_path, capsys, monkeypatch, name, fault):
    if fault is not None:
        plant(monkeypatch, fault)
    result = harness.run_cell(tiny_cell(name), seed=2**31 + 101, seconds=0.05,
                              trace=False, t_start=time.perf_counter(),
                              run_dir=tmp_path / "run", require_chip=False)
    capsys.readouterr()
    assert result["correct"] is (fault is None), result["check"]
