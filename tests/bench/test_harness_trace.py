"""bench/trace.py: the reduction from a profiler trace to device metrics,
on hand-built timelines and on a small trace recorded on a TPU v5e chip
(``bench/testdata/record.py``)."""

from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parents[2] / "bench" / "testdata" / "grouping_2win.xplane.pb"
DEV = "/device:TPU:0"


def ev(name, s, e):
    return tr.Event(name, s, e)


def toy():
    dev = [ev("%moments_edges_stats.1 = (f32[8,8]) custom-call(f32[8,128] %x)", 10, 30),
           ev("%copy.2 = f32[8,128] copy(f32[8,128] %y)", 25, 40),
           ev("%fit_error_counts.3.clone = f32[8,4] custom-call(%p)", 60, 70),
           ev("%fusion.4 = f32[8] fusion(%q), kind=kLoop", 95, 130)]
    mods = [ev("jit_moments_f(123)", 5, 41), ev("jit_fit_pred_f(9)", 55, 131)]
    host = [ev("bench.window", 0, 100), ev("PjitFunction(gather_f)", 38, 58),
            ev("PjitFunction(fit_pred_f)", 55, 61), ev("np.asarray(jax.Array)", 70, 90)]
    return tr.Trace({DEV: dev}, host, (0, 100), {DEV: mods})


def test_busy_is_the_union_inside_the_window():
    t = toy()
    assert tr.union([(10, 30), (25, 40), (60, 70)]) == [(10, 40), (60, 70)]
    assert tr.busy_seconds(t, 1) == pytest.approx((30 + 10 + 5) / 1e9)
    assert tr.window_seconds(t) == pytest.approx(100 / 1e9)


def test_kernel_time_by_instruction_name():
    t = toy()
    assert tr.kernel_seconds(t, 1, "moments_edges_stats") == pytest.approx(20 / 1e9)
    assert tr.kernel_seconds(t, 1, "fit_error_counts") == pytest.approx(10 / 1e9)
    assert tr.kernel_seconds(t, 1, "copy") == pytest.approx(15 / 1e9)
    assert tr.kernel_seconds(t, 1, "fit") == 0


def test_idle_gaps_are_charged_to_the_main_thread():
    t = toy()
    gaps = tr.idle_gaps(t, 1)
    assert gaps[0] == ["np.asarray(jax.Array)", pytest.approx(25 / 1e9)]  # covers 20 of 25
    assert gaps[1] == ["PjitFunction(gather_f)", pytest.approx(20 / 1e9)]
    assert gaps[2] == [tr.UNTRACED, pytest.approx(10 / 1e9)]
    assert sum(g[1] for g in gaps) + tr.busy_seconds(t, 1) == pytest.approx(tr.window_seconds(t))


def test_top_ops_are_named_by_module_and_instruction():
    ops = dict(tr.top_ops(toy(), 1))
    assert ops == pytest.approx({"jit_moments_f/moments_edges_stats": 20e-9,
                                 "jit_moments_f/copy": 15e-9,
                                 "jit_fit_pred_f/fit_error_counts": 10e-9,
                                 "jit_fit_pred_f/fusion": 5e-9})


@pytest.fixture(scope="module")
def recorded():
    return tr.load(DATA)


def test_recorded_trace_window_and_busy(recorded):
    assert list(recorded.devices) == [DEV]
    window, busy = tr.window_seconds(recorded), tr.busy_seconds(recorded, 1)
    assert 0 < busy < window
    gaps = tr.idle_gaps(recorded, 1, k=10**6)
    assert sum(g[1] for g in gaps) + busy == pytest.approx(window, rel=1e-9)


def test_recorded_trace_finds_one_launch_per_window(recorded):
    # two 25-line windows, method 'grouping': one moments and one fit launch each
    for kernel in ("moments_edges_stats", "fit_error_counts"):
        events = tr.kernel_events(recorded, 1, kernel)
        assert len(events) == 2, kernel
        assert 0 < tr.kernel_seconds(recorded, 1, kernel) < tr.busy_seconds(recorded, 1)
    names = [name for name, _ in tr.top_ops(recorded, 1)]
    assert "jit_moments_f/moments_edges_stats" in names
    assert "jit_fit_all_f/fit_error_counts" in names
