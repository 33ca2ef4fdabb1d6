"""Named host spans (runtime.monitor.SpanRecorder): totals and counts with
the profiler off and on, the executor's stage totals taken from the spans'
own clock reads, the session's sums, and the spans of a small run as the
profiler records them: on the main, prefetch and writer threads, linked by
their ``slice`` and ``line`` arguments, with no main-thread span inside
another."""

import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import (ComputeSpec, ExecSpec, MethodSpec, PDFSession,
                       PipelineSpec, SourceSpec)
from repro.runtime.monitor import SpanRecorder, merge_totals

MAIN_SPANS = {"pdf.session.open", "pdf.executor.build", "pdf.slice.open",
              "pdf.load.first", "pdf.load.wait", "pdf.moments", "pdf.select",
              "pdf.fit.launch", "pdf.fit.wait", "pdf.handoff", "pdf.slice.drain"}
PREFETCH_SPANS = {"pdf.load.read", "pdf.load.h2d"}
WRITER_SPANS = {"pdf.persist.write"}


class LoggingRecorder(SpanRecorder):
    """Keeps every span it opened, with its arguments and clock reads."""

    def __init__(self):
        super().__init__()
        self.log = []

    def span(self, name, start=None, **ids):
        sp = super().span(name, start, **ids)
        self.log.append((name, ids, sp))
        return sp


def spec(tmp_path, method="grouping_ml", **execution):
    return PipelineSpec(
        source=SourceSpec(num_slices=3, lines_per_slice=12, points_per_line=20,
                          observations=120),
        method=MethodSpec(name=method),
        compute=ComputeSpec(window_lines=4),
        execution=ExecSpec(slices=(0, 1), out_dir=str(tmp_path / "out"), **execution),
    )


def record(rec: SpanRecorder):
    with rec.span("a", slice=1, line=2) as a:
        time.sleep(0.002)
    with rec.span("b", start=a.end) as b:
        pass
    with rec.span("a", slice=1, line=3):
        pass
    rec.count("rows", 5)
    rec.count("rows", 2)
    return a, b


@pytest.mark.parametrize("profiler", [False, True])
def test_span_totals_and_counts(tmp_path, profiler):
    rec = SpanRecorder()
    if profiler:
        jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        a, b = record(rec)
    finally:
        if profiler:
            jax.profiler.stop_trace()
    assert a.seconds >= 0.002 and b.start == a.end and b.end >= b.start
    assert set(rec.spans) == {"a", "b"}
    assert rec.spans["a"][1] == 2 and rec.spans["b"] == (b.seconds, 1)
    assert rec.spans["a"][0] >= a.seconds
    assert rec.counters == {"rows": 7}
    if profiler:
        (xplane,) = (tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb")
        events = [(e.name, dict(e.stats)) for p in ProfileData.from_file(str(xplane)).planes
                  for line in p.lines for e in line.events if e.name in ("a", "b")]
        assert ("a", {"slice": 1, "line": 2}) in events
        assert ("a", {"slice": 1, "line": 3}) in events
        assert [n for n, _ in events].count("b") == 1


def test_since_and_merge_totals():
    rec = SpanRecorder()
    record(rec)
    snap = rec.snapshot()
    with rec.span("b"):
        pass
    rec.count("rows", 4)
    spans, counters = rec.since(snap)
    assert set(spans) == {"b"} and spans["b"][1] == 1 and counters == {"rows": 4}
    into_s, into_c = {"b": (1.0, 2)}, {"rows": 1, "other": 3}
    merge_totals(into_s, into_c, spans, counters)
    assert into_s["b"] == (pytest.approx(1.0 + spans["b"][0]), 3)
    assert into_c == {"rows": 5, "other": 3}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    s = PDFSession(spec(tmp_path_factory.mktemp("tree")))
    return s.tree


@pytest.mark.parametrize("method", ["grouping_ml", "baseline"])
def test_report_fields_are_the_spans_reads(tmp_path, tree, method):
    session = PDFSession(spec(tmp_path, method), tree=tree)
    ex = session.executor()
    ex.spans = LoggingRecorder()
    results = list(session.run())
    log = ex.spans.log
    windows = [ws for r in results for ws in r.stats]

    def spans(name, ws=None):
        return [sp for n, ids, sp in log if n == name and (
            ws is None or ids == {"slice": ws.window.slice_i, "line": ws.window.line_start})]

    for ws in windows:
        (read,), (h2d,), (wait,) = spans("pdf.load.read", ws), spans("pdf.load.h2d", ws), \
            spans("pdf.load.first", ws) + spans("pdf.load.wait", ws)
        assert h2d.start == read.end
        assert ws.load_seconds == h2d.end - read.start
        assert ws.wait_seconds == wait.seconds
        steps = [sp for name in ("pdf.select", "pdf.fit.launch", "pdf.fit.wait")
                 for sp in spans(name, ws)]
        (moments,) = spans("pdf.moments", ws)
        # the compute stage starts at the moments span's end read, and its
        # spans tile it from there
        first, last = min(steps, key=lambda sp: sp.start), max(steps, key=lambda sp: sp.end)
        assert first.start == moments.end
        assert sum(sp.seconds for sp in steps) == pytest.approx(last.end - first.start)
        assert 0 <= ws.compute_seconds - (last.end - first.start) < 1e-3

    rep = ex.last_report  # the last slice's run
    last_slice = [ws for ws in windows if ws.window.slice_i == 1]
    assert rep.units == len(last_slice) == rep.counters["windows"]
    assert rep.wait_seconds == pytest.approx(sum(ws.wait_seconds for ws in last_slice))
    assert rep.persist_seconds == pytest.approx(rep.spans["pdf.persist.write"][0])
    assert rep.load_seconds == pytest.approx(
        rep.spans["pdf.load.read"][0] + rep.spans["pdf.load.h2d"][0])
    assert rep.spans["pdf.slice.open"][1] == rep.spans["pdf.slice.drain"][1] == 1
    sr = session.report()
    assert sr.persist_seconds == pytest.approx(sr.spans["pdf.persist.write"][0])
    assert sr.load_seconds == pytest.approx(
        sr.spans["pdf.load.read"][0] + sr.spans["pdf.load.h2d"][0])
    assert sr.compute_seconds >= sum(sr.spans[n][0] for n in
                                     ("pdf.select", "pdf.fit.launch", "pdf.fit.wait")
                                     if n in sr.spans)


@pytest.fixture(scope="module")
def default_results(tmp_path_factory, tree):
    return list(PDFSession(spec(tmp_path_factory.mktemp("default")), tree=tree).run())


@pytest.mark.parametrize("prefetch", [True, False])
def test_first_window_wait_has_its_own_span(tmp_path, tree, default_results, prefetch):
    # each run's (each slice's) wait for its first window is pdf.load.first;
    # it still counts in the window's and the report's wait_seconds
    session = PDFSession(spec(tmp_path, prefetch=prefetch), tree=tree)
    ex = session.executor()
    ex.spans = LoggingRecorder()
    results = list(session.run())
    rep = session.report()
    runs = rep.spans["pdf.slice.open"][1]
    assert runs == 2 and rep.spans["pdf.load.first"][1] == runs
    firsts = [(ids["slice"], ids["line"]) for n, ids, _sp in ex.spans.log
              if n == "pdf.load.first"]
    assert firsts == [(0, 0), (1, 0)]
    windows = [ws for r in results for ws in r.stats]
    waits = {(ids["slice"], ids["line"]): sp.seconds for n, ids, sp in ex.spans.log
             if n in ("pdf.load.first", "pdf.load.wait") and ids["line"] >= 0}
    assert len(waits) == len(windows)
    for ws in windows:
        assert ws.wait_seconds == waits[(ws.window.slice_i, ws.window.line_start)]
    assert rep.wait_seconds == pytest.approx(sum(ws.wait_seconds for ws in windows))
    last = [ws for ws in windows if ws.window.slice_i == 1]
    assert ex.last_report.wait_seconds == pytest.approx(sum(ws.wait_seconds for ws in last))
    for got, want in zip(results, default_results):
        for name in ("type_idx", "params", "error", "mean", "std", "skew", "kurt"):
            np.testing.assert_array_equal(getattr(got, name), getattr(want, name))


def test_session_report_sums_spans_and_counters(tmp_path, tree):
    session = PDFSession(spec(tmp_path), tree=tree)
    for _ in session.run():
        pass
    rep = session.report()
    points = 2 * 12 * 20
    c = rep.counters
    assert c["windows"] == rep.windows == 6 and c["points"] == points
    assert c["bytes_read"] == c["bytes_h2d"] == points * 120 * 4
    assert 0 < c["groups"] <= points and c["fit_rows"] <= c["groups"]
    assert c["fit_rows"] < c["fit_rows_padded"]
    per_call = {"pdf.session.open": 1, "pdf.executor.build": 1, "pdf.slice.open": 2,
                "pdf.slice.drain": 2, "pdf.moments": 6, "pdf.select": 6,
                "pdf.fit.launch": 6, "pdf.fit.wait": 6, "pdf.handoff": 6,
                "pdf.load.read": 6, "pdf.load.h2d": 6, "pdf.persist.write": 6,
                "pdf.load.first": 2, "pdf.load.wait": 6}
    assert {k: n for k, (_s, n) in rep.spans.items()} == per_call
    totals = {}
    for reports in rep.shard_reports.values():
        for r in reports:
            for k, (s, _n) in r.spans.items():
                totals[k] = totals.get(k, 0.0) + s
    for k, s in totals.items():
        assert rep.spans[k][0] == pytest.approx(s)


def test_profiled_run_names_each_thread(tmp_path, tree):
    s = spec(tmp_path)
    for _ in PDFSession(s, tree=tree).run():  # compile outside the trace
        pass
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for _ in PDFSession(spec(tmp_path / "2"), tree=tree).run():
            pass
    finally:
        jax.profiler.stop_trace()
    (xplane,) = (tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            evs = [(e.name, dict(e.stats), int(e.start_ns), int(e.end_ns))
                   for e in line.events if e.name.startswith("pdf.")]
            if evs:
                lines.append(evs)
    # one main thread; a prefetch and a writer thread per slice. Each line
    # holds one role's spans, except where the OS gave a finished thread's id
    # to a later slice's thread of the other role: then the line is two runs
    # of spans, one role each, the first ending before the second starts and
    # of earlier slices only
    role_of = {**{n: "main" for n in MAIN_SPANS}, **{n: "prefetch" for n in PREFETCH_SPANS},
               **{n: "writer" for n in WRITER_SPANS}}
    roles = {"main": [], "prefetch": [], "writer": []}
    for evs in lines:
        names = {n for n, *_ in evs}
        role = ("main" if names == MAIN_SPANS else "prefetch" if names == PREFETCH_SPANS
                else "writer" if names == WRITER_SPANS else None)
        if role is None:
            assert names == PREFETCH_SPANS | WRITER_SPANS, names
            evs = sorted(evs, key=lambda e: e[2])
            cut = next(i for i, e in enumerate(evs) if role_of[e[0]] != role_of[evs[0][0]])
            first, second = evs[:cut], evs[cut:]
            assert len({role_of[n] for n, *_ in second}) == 1, evs
            assert max(e[3] for e in first) <= second[0][2], evs
            assert max(ids["slice"] for _n, ids, *_ in first) < \
                min(ids["slice"] for _n, ids, *_ in second), evs
            for run in (first, second):
                roles[role_of[run[0][0]]] += run
        else:
            roles[role] += evs
    assert sum(1 for evs in lines if {n for n, *_ in evs} == MAIN_SPANS) == 1
    main = sorted(roles["main"], key=lambda e: e[2])
    for a, b in zip(main, main[1:]):  # leaves: each ends before the next starts
        assert a[3] <= b[2], (a, b)

    def window_ids(evs, name):
        return sorted((ids["slice"], ids["line"]) for n, ids, *_ in evs if n == name)

    wins = window_ids(main, "pdf.moments")
    assert len(wins) == 6 and all(line >= 0 for _s, line in wins)
    for name in ("pdf.select", "pdf.fit.launch", "pdf.fit.wait", "pdf.handoff"):
        assert window_ids(main, name) == wins
    assert window_ids(roles["prefetch"], "pdf.load.read") == wins
    assert window_ids(roles["prefetch"], "pdf.load.h2d") == wins
    assert window_ids(roles["writer"], "pdf.persist.write") == wins
    assert window_ids(main, "pdf.slice.open") == [(0, -1), (1, -1)]
