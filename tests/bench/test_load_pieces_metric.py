"""Pieces a window was staged in (``bench/metrics/load_pieces_per_window.py``):
on synthetic session reports with and without the program's
``load_pieces`` counter."""

from types import SimpleNamespace

import pytest

from bench import harness


def report(counters):
    return SimpleNamespace(spans={"pdf.load.read": (0.010, 16)}, counters=counters)


def ctx(calls):
    window = SimpleNamespace(calls=[(f"call{k}", r) for k, r in enumerate(calls)])
    return SimpleNamespace(window=window, trace=None, chips=1, notes={})


# a Set3 call: 12 full windows in 7 pieces and 4 one-line tails in one
SET3 = report({"windows": 16, "points": 76304, "load_pieces": 12 * 7 + 4})
# a Set1 call: every window whole
SET1 = report({"windows": 84, "points": 527100, "load_pieces": 84})


@pytest.mark.parametrize("calls, expected", [
    ([SET3], 5.5), ([SET3, SET3], 5.5), ([SET1], 1.0), ([SET1, SET1], 1.0),
    ([SET3, SET1], (88 + 84) / 100),
])
def test_load_pieces_per_window_reads_the_reports(calls, expected):
    read = harness.metric_reader(harness.ROOT, "load_pieces_per_window")
    assert read(ctx(calls)) == pytest.approx(expected)


def test_load_pieces_per_window_without_the_counter_reads_nothing():
    # a program without the counter: spans and windows, but no load_pieces
    parent = [report({k: v for k, v in r.counters.items() if k != "load_pieces"})
              for r in (SET3, SET1)]
    read = harness.metric_reader(harness.ROOT, "load_pieces_per_window")
    assert read(ctx(parent)) is None
    assert read(ctx([])) is None
    old = SimpleNamespace(wall_seconds=1.0, windows=2, wait_seconds=0.1)
    assert read(ctx([old])) is None
    assert read(ctx([report({"windows": 0, "load_pieces": 0})])) is None


def test_warm_up_compiles_the_assembly(tmp_path, capsys, monkeypatch):
    """A tiny cell whose 3-line windows are staged a line a piece: the
    set-up's warm-up window takes the same pieces, so the window compiles
    nothing, and the metric reads the pieces."""
    import json
    import shutil
    import time

    from repro.core import executor

    from tests.bench.test_harness_data_driven import LOOSE, TINY, make_root

    root = make_root(tmp_path, "grouping_ml", "dup", LOOSE)
    shutil.copy(harness.ROOT / "bench" / "metrics" / "load_pieces_per_window.py",
                root / "bench" / "metrics")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "load_pieces_per_window", "unit": "count"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line_bytes = TINY["points_per_line"] * TINY["observations"] * 4
    monkeypatch.setattr(executor, "_MIN_PIECE_BYTES", line_bytes)
    cell = harness.load_cell("tiny.mix", root)
    result = harness.run_cell(cell, seed=2**31 + 11, seconds=0.2, trace=True,
                              t_start=time.perf_counter(), run_dir=root / "run",
                              require_chip=False)
    capsys.readouterr()
    assert result["correct"] is True
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["metrics"]["load_pieces_per_window"]["value"] == TINY["window_lines"]
