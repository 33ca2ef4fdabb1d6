"""The per-layer metrics of the executor's load added with the Set3 cell:
the wait for a run's first window (``pdf.load.first``), and the rates of
the file read and of the host-to-device copy (bytes counted over their
spans' seconds). On synthetic session reports, each reads its number, and
nothing where its span or counter is absent, as in a program without
them."""

from types import SimpleNamespace

import pytest

from bench import harness


def report(spans, counters):
    return SimpleNamespace(spans=spans, counters=counters)


CALLS = [
    report({"pdf.load.first": (0.100, 2), "pdf.load.read": (0.050, 4),
            "pdf.load.h2d": (0.020, 4)},
           {"windows": 4, "bytes_read": 400_000_000, "bytes_h2d": 400_000_000}),
    report({"pdf.load.first": (0.140, 2), "pdf.load.read": (0.030, 4),
            "pdf.load.h2d": (0.030, 4)},
           {"windows": 4, "bytes_read": 80_000_000, "bytes_h2d": 100_000_000}),
]

EXPECTED = {
    "first_wait_ms_per_slice": 60.0,  # 240 ms over 4 runs
    "load_read_gb_per_s": 6.0,  # 480 MB in 80 ms
    "load_h2d_gb_per_s": 10.0,  # 500 MB in 50 ms
}

# what each reader needs: its span, and its counter
NEEDS = {
    "first_wait_ms_per_slice": ("pdf.load.first", None),
    "load_read_gb_per_s": ("pdf.load.read", "bytes_read"),
    "load_h2d_gb_per_s": ("pdf.load.h2d", "bytes_h2d"),
}


def ctx(calls):
    window = SimpleNamespace(calls=[(f"call{k}", r) for k, r in enumerate(calls)])
    return SimpleNamespace(window=window, trace=None, chips=1, notes={})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_load_metric_reads_the_reports(name):
    read = harness.metric_reader(harness.ROOT, name)
    assert read(ctx(CALLS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_load_metric_reads_nothing_without_its_span_or_counter(name):
    read = harness.metric_reader(harness.ROOT, name)
    span, counter = NEEDS[name]
    no_span = [report({k: v for k, v in r.spans.items() if k != span}, r.counters)
               for r in CALLS]
    assert read(ctx(no_span)) is None
    if counter is not None:
        no_counter = [report(r.spans, {k: v for k, v in r.counters.items() if k != counter})
                      for r in CALLS]
        assert read(ctx(no_counter)) is None
    old = SimpleNamespace(wall_seconds=1.0, windows=2, wait_seconds=0.1)
    assert read(ctx([old])) is None
    assert read(ctx([])) is None
