"""The share of windows read into a recycled host buffer
(``bench/metrics/read_recycled_share.py``): on synthetic session reports with
and without the program's ``read_recycled`` counter."""

from types import SimpleNamespace

import pytest

from bench import harness


def report(counters):
    return SimpleNamespace(spans={"pdf.load.read": (0.010, 2)}, counters=counters)


def ctx(calls):
    window = SimpleNamespace(calls=[(f"call{k}", r) for k, r in enumerate(calls)])
    return SimpleNamespace(window=window, trace=None, chips=1, notes={})


CALLS = [report({"windows": 2, "points": 100, "read_recycled": 1}),
         report({"windows": 2, "points": 100, "read_recycled": 2})]


@pytest.mark.parametrize("calls, expected", [(CALLS, 75.0), (CALLS[:1], 50.0),
                                             (CALLS[1:], 100.0)])
def test_read_recycled_share_reads_the_reports(calls, expected):
    read = harness.metric_reader(harness.ROOT, "read_recycled_share")
    assert read(ctx(calls)) == pytest.approx(expected)


def test_read_recycled_share_without_the_counter_reads_nothing():
    # a program without the counter: spans and windows, but no read_recycled
    parent = [report({k: v for k, v in r.counters.items() if k != "read_recycled"})
              for r in CALLS]
    read = harness.metric_reader(harness.ROOT, "read_recycled_share")
    assert read(ctx(parent)) is None
    assert read(ctx([])) is None
    old = SimpleNamespace(wall_seconds=1.0, windows=2, wait_seconds=0.1)
    assert read(ctx([old])) is None
