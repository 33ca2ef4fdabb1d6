"""Staged executor: plans, bitwise equivalence vs the serial reference path,
prefetch overlap + error propagation, async persist / resume, slice
scheduling across shards."""

import time

import numpy as np
import pytest

from repro.core import distributions as d
from repro.core.executor import ExecutorConfig, PDFConfig, StagedExecutor
from repro.core.pipeline import PDFComputer, train_type_tree
from repro.core.regions import CubeGeometry, WorkUnit, build_plan
from repro.data.loader import PrefetchError, ThrottledSource, WindowPrefetcher
from repro.data.simulation import SeismicSimulation, SimulationConfig
from repro.runtime.scheduler import SliceScheduler, assign_slices

# the pre-refactor strictly serial loop: the reference all staged
# configurations must match bitwise
SERIAL = ExecutorConfig(prefetch=False, async_persist=False)

RESULT_FIELDS = ("type_idx", "params", "error", "mean", "std", "skew", "kurt")


@pytest.fixture(scope="module")
def sim():
    return SeismicSimulation(
        SimulationConfig(geometry=CubeGeometry(8, 9, 12), num_simulations=250)
    )


@pytest.fixture(scope="module")
def tree(sim):
    return train_type_tree(sim, window_lines=3)


def assert_results_equal(a, b):
    for f in RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)
    assert a.avg_error == b.avg_error


# -- plans ---------------------------------------------------------------------


def test_build_plan_covers_slices_in_order():
    geom = CubeGeometry(4, 10, 7)
    plan = build_plan(geom, [2, 0], window_lines=4)
    assert plan.slices == (2, 0)
    assert [u.seq for u in plan.units] == list(range(len(plan)))
    # windows of each slice are disjoint, ordered, and cover all lines
    for s in (2, 0):
        ws = [u.window for u in plan.units_for_slice(s)]
        assert ws[0].line_start == 0 and ws[-1].line_end == 10
        for prev, nxt in zip(ws, ws[1:]):
            assert prev.line_end == nxt.line_start


def test_build_plan_start_lines_and_bounds():
    geom = CubeGeometry(4, 10, 7)
    plan = build_plan(geom, [1, 3], window_lines=5, start_lines={1: 5, 3: 10})
    assert [u.window.slice_i for u in plan.units] == [1]  # slice 3 complete
    assert plan.units[0].window.line_start == 5
    with pytest.raises(ValueError):
        build_plan(geom, [4], window_lines=5)


# -- equivalence ---------------------------------------------------------------


@pytest.mark.parametrize(
    "method", ["baseline", "grouping", "reuse", "ml", "grouping_ml", "reuse_ml"]
)
def test_methods_bitwise_identical_to_serial_path(sim, tree, method):
    cfg = PDFConfig(window_lines=3, method=method)
    t = tree if "ml" in method else None
    serial = PDFComputer(cfg, sim, tree=t, exec_config=SERIAL).run_slice(2)
    staged = PDFComputer(cfg, sim, tree=t).run_slice(2)  # prefetch + async
    assert_results_equal(serial, staged)


def test_multi_slice_plan_matches_sequential_slices(sim):
    """One plan spanning slices == consecutive run_slice calls on one
    computer (the reuse cache crosses slice boundaries identically)."""
    cfg = PDFConfig(window_lines=3, method="reuse")
    seq = PDFComputer(cfg, sim, exec_config=SERIAL)
    expected = {s: seq.run_slice(s) for s in (2, 3)}

    ex = StagedExecutor(cfg, sim)
    got = ex.run(build_plan(sim.geometry, [2, 3], 3))
    assert set(got) == {2, 3}
    for s in (2, 3):
        assert_results_equal(expected[s], got[s])
    assert ex.last_report is not None
    assert ex.last_report.units == len(got[2].stats) + len(got[3].stats)


# -- prefetcher ----------------------------------------------------------------


def test_prefetcher_preserves_order():
    pf = WindowPrefetcher(range(20), lambda i: i * i, depth=3)
    assert list(pf) == [i * i for i in range(20)]


def test_prefetcher_propagates_stage_errors():
    def boom(i):
        if i == 3:
            raise ValueError("bad window")
        return i

    pf = WindowPrefetcher(range(10), boom, depth=2)
    with pytest.raises(PrefetchError) as ei:
        list(pf)
    assert isinstance(ei.value.__cause__, ValueError)


def test_prefetcher_close_unblocks_producer():
    pf = WindowPrefetcher(range(1000), lambda i: i, depth=1)
    it = iter(pf)
    assert next(it) == 0
    pf.close()  # producer is blocked on the full queue; must not deadlock
    assert not pf._thread.is_alive()


def test_prefetch_overlaps_throttled_load(sim):
    """Through an NFS-modeled source, the compute stage must block on less
    than the full load time (the first window is never hidden, later ones
    are) — the 'device not blocked on load_window' property."""
    nfs = ThrottledSource(sim, bandwidth_bytes_per_s=4e6)  # ~3ms per window
    cfg = PDFConfig(window_lines=3, method="baseline")
    comp = PDFComputer(cfg, nfs)
    comp.run_slice(1)  # jit warmup
    res = comp.run_slice(2)
    rep = comp.last_report
    assert rep.load_seconds > 0
    assert res.total_wait_seconds < res.total_load_seconds
    assert rep.load_hidden_seconds > 0


def test_throttled_source_paces_reads(sim):
    import time

    w = build_plan(sim.geometry, [0], 3).units[0].window
    raw = sim.load_window(w)
    bw = raw.nbytes / 0.02  # ~20ms per window
    t0 = time.perf_counter()
    block = ThrottledSource(sim, bw).load_window(w)
    assert time.perf_counter() - t0 >= 0.015
    np.testing.assert_array_equal(block, raw)


# -- persist / resume ----------------------------------------------------------


def test_crash_mid_slice_resume_identical(sim, tmp_path):
    """Crash mid-slice, re-run with resume=True: results identical to an
    uninterrupted run, completed windows not re-done — through the fully
    staged pipeline (prefetch + async persist)."""
    cfg = PDFConfig(window_lines=3, method="grouping")
    full = PDFComputer(cfg, sim, out_dir=tmp_path / "full").run_slice(5)

    out = tmp_path / "crash"
    seen = 0

    class Crash(Exception):
        pass

    def crash_after_two(ws):
        nonlocal seen
        seen += 1
        if seen == 2:
            raise Crash()

    with pytest.raises(Crash):
        PDFComputer(cfg, sim, out_dir=out).run_slice(5, on_window=crash_after_two)

    resumed = PDFComputer(cfg, sim, out_dir=out).run_slice(5, resume=True)
    assert_results_equal(full, resumed)
    # the two completed windows were restored from .npz, not re-run
    assert len(resumed.stats) == len(full.stats) - 2


def test_async_persist_watermark_and_files_consistent(sim, tmp_path):
    cfg = PDFConfig(window_lines=4, method="baseline")
    comp = PDFComputer(cfg, sim, out_dir=tmp_path)
    res = comp.run_slice(3)
    assert comp._watermark(3) == sim.geometry.lines_per_slice
    files = sorted(tmp_path.glob("slice3_window_*.npz"))
    assert len(files) == len(res.stats)
    ppl = sim.geometry.points_per_line
    for f in files:
        z = np.load(f)
        lo, hi = int(z["line_start"]) * ppl, int(z["line_end"]) * ppl
        np.testing.assert_array_equal(z["error"], res.error[lo:hi])
        np.testing.assert_array_equal(z["type_idx"], res.type_idx[lo:hi])


def test_persist_failure_surfaces(sim, tmp_path):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file where the out_dir must go")
    comp = PDFComputer(PDFConfig(window_lines=4), sim, out_dir=blocker)
    with pytest.raises(RuntimeError, match="persist stage failed"):
        comp.run_slice(1)


# -- scheduler -----------------------------------------------------------------


def test_assign_slices_round_robin_balance():
    a = assign_slices(list(range(10)), 3)
    assert [x.slices for x in a] == [(0, 3, 6, 9), (1, 4, 7), (2, 5, 8)]
    sizes = [len(x.slices) for x in a]
    assert max(sizes) - min(sizes) <= 1
    with pytest.raises(ValueError):
        assign_slices([0], 0)


def test_scheduler_runs_all_shards_and_matches_direct(sim):
    cfg = PDFConfig(window_lines=3, method="grouping")
    direct = {
        s: PDFComputer(cfg, sim, exec_config=SERIAL).run_slice(s) for s in (1, 2, 3)
    }
    sched = SliceScheduler(num_shards=2)
    results = sched.run(
        lambda shard: StagedExecutor(cfg, sim), [1, 2, 3]
    )
    assert set(results) == {1, 2, 3}
    for s in (1, 2, 3):
        assert_results_equal(direct[s], results[s])
    assert set(sched.last_reports) == {0, 1}
    assert sched.window_monitor.completed == sum(len(r.stats) for r in results.values())


def test_scheduler_single_shard_mode(sim):
    cfg = PDFConfig(window_lines=3, method="baseline")
    sched = SliceScheduler(num_shards=2)
    results = sched.run(
        lambda shard: StagedExecutor(cfg, sim), [1, 2, 3, 4], shard=1
    )
    # shard 1 owns slices [2, 4] under round-robin of [1,2,3,4]
    assert set(results) == {2, 4}


# -- recycled read buffers -----------------------------------------------------


@pytest.fixture(scope="module")
def file_cube(sim, tmp_path_factory):
    """The module's simulation exported in chunks of 4 lines, so 3-line
    windows straddle chunk boundaries."""
    from repro.data.file_source import export_cube

    d = tmp_path_factory.mktemp("file_cube")
    export_cube(sim, d, lines_per_chunk=4)
    return d


class _Recycling(StagedExecutor):
    """Counts the host read buffers it makes. Unless ``aligned``, each lies
    off a 64-byte boundary: the CPU runtime then copies out of it instead
    of aliasing it, so the free-list is really exercised here. An aligned
    one the CPU runtime makes the device array's own storage, and the
    executor must then keep it off the free-list."""

    aligned = False

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.made = []  # appended from the loading threads

    def _new_buffer(self, size):
        raw = np.empty(size + 16, np.float32)
        k = next(k for k in range(16)
                 if ((raw.ctypes.data + 4 * k) % 64 == 0) == self.aligned)
        self.made.append(raw[k:k + size])
        return self.made[-1]


class _SlowOnce:
    """Delays the first read of one window, so a straggler speculation
    fires (FileCubeSource with ``out`` kept: it still takes buffers)."""

    def __init__(self, inner, window, seconds):
        self.inner, self.window, self.seconds = inner, window, seconds
        self.geometry = inner.geometry
        self.slice_observations = inner.slice_observations

    def load_window(self, w, out=None):
        if w == self.window and self.seconds:
            seconds, self.seconds = self.seconds, 0
            time.sleep(seconds)
        return self.inner.load_window(w, out=out)


@pytest.mark.parametrize("mode", ["prefetch", "serial", "speculate"])
def test_recycled_buffers_bitwise_identical(sim, tree, file_cube, mode):
    from repro.data.file_source import FileCubeSource

    cfg = PDFConfig(window_lines=3, method="grouping_ml")
    slices = [0, 1, 2, 3]
    ref = StagedExecutor(cfg, sim, tree=tree, exec_config=SERIAL).run(
        build_plan(sim.geometry, slices, 3))
    src = FileCubeSource(file_cube)
    ec = {"prefetch": ExecutorConfig(speculate=False),
          "serial": SERIAL,
          "speculate": ExecutorConfig(prefetch=False, speculate=True,
                                      straggler_grace_s=0.05,
                                      retry_backoff_s=0.001)}[mode]
    if mode == "speculate":
        # the 9th unit: the trailing load median exists by then
        src = _SlowOnce(src, build_plan(sim.geometry, slices, 3).units[8].window, 1.0)
    ex = _Recycling(cfg, src, tree=tree, exec_config=ec)
    got = ex.run(build_plan(sim.geometry, slices, 3))
    for s in slices:
        assert_results_equal(ref[s], got[s])
    rep = ex.last_report
    assert rep.counters["read_recycled"] > 0
    if mode == "speculate":
        assert rep.speculations > 0
        assert len(ex.made) >= 2  # the straggler's two loads, two buffers


@pytest.mark.parametrize("aligned", [False, True])
def test_recycled_buffer_never_overwritten_before_landing(sim, file_cube, aligned):
    """Depth 1 and many one-line windows: each staged window, as the compute
    stage takes it, equals a fresh read; and every read but those into a new
    buffer landed in a recycled one."""
    from repro.data.file_source import FileCubeSource

    src = FileCubeSource(file_cube)
    mismatched = []

    class Checked(_Recycling):
        def _compute_window(self, item, attempt=0):
            if not np.array_equal(np.asarray(item.values),
                                  src.load_window(item.unit.window)):
                mismatched.append(item.unit.window)
            return super()._compute_window(item, attempt)

    Checked.aligned = aligned
    cfg = PDFConfig(window_lines=1, method="grouping")
    ex = Checked(cfg, FileCubeSource(file_cube),
                 exec_config=ExecutorConfig(prefetch_depth=1, speculate=False))
    ex.run(build_plan(sim.geometry, list(range(8)), 1))
    assert mismatched == []
    c = ex.last_report.counters
    assert c["windows"] == 8 * sim.geometry.lines_per_slice
    assert c.get("read_recycled", 0) == c["windows"] - len(ex.made)
    if not aligned:
        assert len(ex.made) == 1
