"""The per-layer metrics that read the program's spans and counters
(``bench/spans.py``) and the untraced share of the device's idle time
(``bench/span_trace.py``): on synthetic session reports, on reports of a
program without spans (they read nothing, and do not raise), and on
hand-built timelines."""

from types import SimpleNamespace

import pytest

from bench import harness, span_trace
from bench import trace as tr

DEV = "/device:TPU:0"


def report(spans, counters):
    return SimpleNamespace(spans=spans, counters=counters)


CALLS = [
    report({"pdf.load.read": (0.010, 2), "pdf.load.h2d": (0.002, 2),
            "pdf.moments": (0.004, 2), "pdf.select": (0.006, 2),
            "pdf.fit.launch": (0.001, 2), "pdf.fit.wait": (0.008, 2),
            "pdf.handoff": (0.003, 2), "pdf.slice.open": (0.002, 1),
            "pdf.slice.drain": (0.004, 1), "pdf.session.open": (0.020, 1),
            "pdf.executor.build": (0.010, 1)},
           {"windows": 2, "points": 100, "fit_rows": 75, "fit_rows_padded": 100}),
    report({"pdf.load.read": (0.030, 2), "pdf.load.h2d": (0.002, 2),
            "pdf.moments": (0.004, 2), "pdf.select": (0.002, 2),
            "pdf.fit.launch": (0.003, 2), "pdf.fit.wait": (0.004, 2),
            "pdf.handoff": (0.001, 2), "pdf.slice.open": (0.004, 2),
            "pdf.slice.drain": (0.002, 2), "pdf.session.open": (0.040, 1),
            "pdf.executor.build": (0.010, 1)},
           {"windows": 2, "points": 100, "fit_rows": 75, "fit_rows_padded": 100}),
]

EXPECTED = {
    "load_read_ms_per_window": 10.0,
    "load_h2d_ms_per_window": 1.0,
    "moments_ms_per_window": 2.0,
    "select_ms_per_window": 2.0,
    "fit_launch_ms_per_window": 1.0,
    "fit_wait_ms_per_window": 3.0,
    "handoff_ms_per_window": 1.0,
    "slice_edge_ms_per_slice": 4.0,
    "session_open_ms_per_call": 40.0,
    "fit_pad_share": 25.0,
}


def ctx(calls, trace=None):
    window = SimpleNamespace(calls=[(f"call{k}", r) for k, r in enumerate(calls)])
    return SimpleNamespace(window=window, trace=trace, chips=1, notes={})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_metric_reads_the_reports(name):
    read = harness.metric_reader(harness.ROOT, name)
    assert read(ctx(CALLS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED) + ["untraced_idle_share"])
def test_span_metric_reads_nothing_without_spans(name):
    # the reports of a program that records no spans, and a trace with no
    # pdf.* span on its main line
    old = SimpleNamespace(wall_seconds=1.0, windows=2, wait_seconds=0.1)
    trace = {"trace": tr.Trace({DEV: [tr.Event("%k = f32[] custom-call()", 10, 20)]},
                               [tr.Event("bench.window", 0, 100)], (0, 100))}
    read = harness.metric_reader(harness.ROOT, name)
    assert read(ctx([old], trace)) is None
    assert read(ctx([], None)) is None


def test_fit_pad_share_without_fit_reads_nothing():
    read = harness.metric_reader(harness.ROOT, "fit_pad_share")
    assert read(ctx([report({}, {"windows": 2})])) is None


def toy():
    dev = [tr.Event("%moments_edges_stats.1 = f32[8] custom-call()", 10, 20),
           tr.Event("%fit_error_counts.2 = f32[8] custom-call()", 40, 50),
           tr.Event("%copy.3 = f32[8] copy()", 45, 60)]
    host = [tr.Event("bench.window", 0, 100),
            tr.Event("pdf.load.wait", 0, 8),
            tr.Event("pdf.moments", 8, 22),
            tr.Event("pdf.select", 22, 30),
            tr.Event("np.asarray(jax.Array)", 30, 35),  # not a program span
            tr.Event("pdf.fit.launch", 35, 40),
            tr.Event("pdf.fit.wait", 40, 65),
            tr.Event("pdf.handoff", 90, 120)]  # runs past the window's end
    return tr.Trace({DEV: dev}, host, (0, 100))


def test_untraced_idle_on_a_hand_built_trace():
    # idle: [0,10) [20,40) [60,100) = 70 ns; no pdf span over [30,35) and
    # [65,90): 5 + 25 = 30 ns
    idle_s, untraced_s = span_trace.untraced_idle(toy(), 1)
    assert idle_s == pytest.approx(70e-9)
    assert untraced_s == pytest.approx(30e-9)
    read = harness.metric_reader(harness.ROOT, "untraced_idle_share")
    assert read(ctx(CALLS, {"trace": toy()})) == pytest.approx(100 * 30 / 70)


def test_interval_helpers():
    assert span_trace.complement([(10, 20), (30, 40)], 0, 50) == [(0, 10), (20, 30), (40, 50)]
    assert span_trace.complement([], 5, 9) == [(5, 9)]
    assert span_trace.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert span_trace.overlap([(0, 10)], []) == 0


SPANS_DATA = (harness.ROOT / "bench" / "testdata" / "grouping_2win_spans.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(SPANS_DATA)


def test_recorded_span_trace_names_the_idle_time(recorded):
    # two 25-line windows through one PDFSession on a v5e chip
    # (bench/testdata/record_spans.py): the session, slice and window spans
    # lie on the main line inside the window, in order and never nested
    spans = [e for e in recorded.host if e.name.startswith("pdf.")]
    assert [e.name for e in spans][:4] == ["pdf.session.open", "pdf.executor.build",
                                         "pdf.slice.open", "pdf.load.wait"]
    assert [e.name for e in spans].count("pdf.moments") == 2
    for a, b in zip(spans, spans[1:]):
        assert a.end <= b.start
    lo, hi = recorded.window
    assert lo <= spans[0].start and spans[-1].end <= hi
    idle_s, untraced_s = span_trace.untraced_idle(recorded, 1)
    busy_s = tr.busy_seconds(recorded, 1)
    assert idle_s + busy_s == pytest.approx(tr.window_seconds(recorded), rel=1e-9)
    assert 0 < untraced_s < 0.1 * idle_s
    gaps = tr.idle_gaps(recorded, 1)
    assert gaps[0][0] == "pdf.load.wait"  # the first window's read
    assert sum(name == tr.UNTRACED for name, _ in gaps) <= 1
    assert all(name.startswith("pdf.") or name == tr.UNTRACED for name, _ in gaps)
    read = harness.metric_reader(harness.ROOT, "untraced_idle_share")
    share = read(ctx(CALLS, {"trace": recorded}))
    assert share == pytest.approx(100 * untraced_s / idle_s)
