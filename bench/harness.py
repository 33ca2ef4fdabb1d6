"""Run one cell: set-up, the measured window, the trace, the check.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* its configuration file (``configs[].file``): the cube's shape, the
  candidate types, bins and window, and the slices of Set1 it carries;
* its traffic file ``bench/traffic/<traffic>.json``: the method and the
  cube's redundancy;
* its workload file ``bench/workloads/<cell>.json``: the check's sample
  size and limits;
* a reader ``bench/metrics/<metric>.py`` for each per-layer metric.

So a later change adds a cell, a configuration, a traffic mix or a metric
as files alone. Set-up makes the cube from the seed, exports it with the
program's exporter and reads it back through ``SourceSpec(kind='file')``;
trains the tree (``*_ml`` methods) with the program's ``train_type_tree``
on Set1 slices 0-3 from the same generator; and warms up every launch
shape the window uses. The window then calls ``PDFSession.run`` over the
whole cube again and again, each call persisting into a directory of its
own, until ``seconds`` have passed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import threading
import time
from concurrent import futures
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import check
from bench import cube as cubemod

ROOT = Path(__file__).resolve().parents[1]


class ChipMissing(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    root: Path
    config: dict
    traffic: dict
    workload: dict
    chips: int
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    workload = json.loads((root / "bench" / "workloads" / f"{name}.json").read_text())

    def applies(m):
        return name in m.get("workloads", cells)

    return Cell(name, root, config, traffic, workload, int(w["chips"]),
                [m for m in bench["end_to_end"] if applies(m)],
                [m for m in bench["per_layer"] if applies(m)])


def metric_reader(root: Path, name: str):
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] != "tpu" or info["count"] < chips):
        raise ChipMissing(
            f"the cell needs {chips} TPU chip(s); JAX found {info['count']} "
            f"{info['platform']} device(s) ({info['kind']})")
    return info


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


# -- the program's side -------------------------------------------------------


def cube_params(cell: Cell, seed: int) -> cubemod.CubeParams:
    """The generator of the source cube (all of Set1's slices), from which
    the cell's cube takes ``set1_slices`` and the tree its training slices."""
    cfg = cell.config
    return cubemod.CubeParams(
        cubemod.Geometry(cfg["source_num_slices"], cfg["lines_per_slice"],
                         cfg["points_per_line"]),
        cfg["observations"], seed, cell.traffic["redundancy"])


def geometry(cfg: dict, num_slices: int):
    from repro.core.regions import CubeGeometry

    return CubeGeometry(num_slices, cfg["lines_per_slice"], cfg["points_per_line"])


def pipeline_spec(cell: Cell, source_spec, out_dir, cache_dir=None):
    from repro.api import (ComputeSpec, ExecSpec, MethodSpec, PipelineSpec,
                           PlacementSpec)

    cfg = cell.config
    shards = cell.chips
    placement = PlacementSpec(shard_devices=tuple(range(shards)) if shards > 1 else None)
    return PipelineSpec(
        source=source_spec,
        method=MethodSpec(name=cell.traffic["method"], rep_bucket=cfg["rep_bucket"],
                          group_tol=cfg["group_tol"]),
        compute=ComputeSpec(types=tuple(cfg["types"]), num_bins=cfg["num_bins"],
                            window_lines=cfg["window_lines"]),
        execution=ExecSpec(slices=tuple(range(cfg["num_slices"])), shards=shards,
                           placement=placement, out_dir=str(out_dir) if out_dir else None,
                           compile_cache_dir=str(cache_dir) if cache_dir else None),
    )


def needs_tree(cell: Cell) -> bool:
    return "ml" in cell.traffic["method"]


@dataclass
class Setup:
    source_spec: object
    tree: object
    gen: cubemod.CubeGenerator
    parts: dict = field(default_factory=dict)


def warm_source(cell: Cell, gen: cubemod.CubeGenerator):
    """One full window per representative bucket the window can meet, and a
    last one-line window: window k of the full ones holds ``counts[k]``
    distinct rows (each repeated), so grouping pads it into the k-th
    ``rep_bucket * 2^k`` class. Rows come from the cell's own generator at
    cells of one point, so distinct rows differ in mean."""
    cfg = cell.config
    wl, ppl = cfg["window_lines"], cfg["points_per_line"]
    p = wl * ppl
    counts = []
    b = cfg["rep_bucket"]
    while True:
        counts.append(min(p, max(1, 3 * b // 4)))
        if b >= p:
            break
        b *= 2
    distinct = cubemod.CubeGenerator(
        cubemod.CubeParams(gen.params.geometry, cfg["observations"], gen.params.seed, "nodup"))
    base = distinct.window(cfg["set1_slices"][0], 0, wl)
    lines = []
    for g in counts:
        lines.append(base[np.arange(p) % g].reshape(wl, ppl, -1))
    lines.append(base[:ppl].reshape(1, ppl, -1))
    values = np.concatenate(lines)
    from repro.core.regions import CubeGeometry

    return cubemod.SliceSource(CubeGeometry(1, values.shape[0], ppl), [values])


class CubeJob:
    """Generate the cell's cube from the seed and export it, on a thread of
    its own, so that it overlaps JAX's start and the tree's training.
    ``result()`` -> the program's ``SourceSpec(kind='file')``; ``abort()``
    stops it between chunks and waits for it."""

    def __init__(self, cell: Cell, seed: int, run_dir: Path):
        self.cell, self.cancel, self.parts = cell, threading.Event(), {}
        self.gen = cubemod.CubeGenerator(cube_params(cell, seed))
        self._pool = futures.ThreadPoolExecutor(1, thread_name_prefix="bench-cube")
        self._future = self._pool.submit(self._run, Path(run_dir) / "cube")

    def _run(self, cube_dir: Path):
        cfg = self.cell.config
        t = time.perf_counter()
        slices = cubemod.generate_slices(self.gen, cfg["set1_slices"], cancel=self.cancel)
        self.parts["generate_s"] = time.perf_counter() - t
        t = time.perf_counter()
        source = cubemod.SliceSource(geometry(cfg, cfg["num_slices"]), slices, self.cancel)
        spec = cubemod.export(source, cube_dir)
        self.parts["export_s"] = time.perf_counter() - t
        return spec

    def result(self):
        try:
            return self._future.result()
        finally:
            self._pool.shutdown()

    def abort(self):
        self.cancel.set()
        futures.wait([self._future])
        self._pool.shutdown()


def setup(cell: Cell, seed: int, run_dir: Path, cache_dir=None, log=print,
          job: CubeJob | None = None) -> Setup:
    """Cube (``job``, started here if not given), tree and warm-up; returns
    what the window needs. ``log`` gets one line per part with its
    seconds."""
    from repro.api import PDFSession

    cfg = cell.config
    job = job or CubeJob(cell, seed, run_dir)
    parts = {}
    tree = None
    try:
        if needs_tree(cell):
            from repro.core.pipeline import train_type_tree

            t = time.perf_counter()
            tc = cfg["tree"]
            train = cubemod.SliceSource(
                geometry(cfg, len(tc["train_slices"])),
                cubemod.generate_slices(job.gen, tc["train_slices"]))
            tree = train_type_tree(train, types=tuple(cfg["types"]),
                                   slices=tuple(range(len(tc["train_slices"]))),
                                   window_lines=tc["window_lines"], depth=tc["depth"],
                                   max_bins=tc["max_bins"])
            del train
            parts["tree_s"] = time.perf_counter() - t
        t = time.perf_counter()
        source_spec = job.result()
        parts["cube_wait_s"] = time.perf_counter() - t
    except BaseException:
        job.abort()
        raise
    parts.update(job.parts)

    t = time.perf_counter()
    wsrc = warm_source(cell, job.gen)
    warm_dir = run_dir / "warm"
    spec = pipeline_spec(cell, source_spec, warm_dir, cache_dir)
    spec = dataclasses.replace(
        spec, execution=dataclasses.replace(spec.execution, slices=(0,)))
    for _ in PDFSession(spec, data_source=wsrc, tree=tree).run():
        pass
    shutil.rmtree(warm_dir, ignore_errors=True)
    parts["warmup_s"] = time.perf_counter() - t
    for k, v in parts.items():
        log(f"[setup] {k}={v}")
    return Setup(source_spec, tree, job.gen, parts)


@dataclass
class Window:
    calls: list  # (call_dir, SessionReport)
    handed_back: list  # (call_dir, slice, line_start, num_points, t, num_fitted)
    counted_points: int
    compile_delta: dict
    t0: float
    t_end: float


def measure(cell: Cell, st: Setup, seconds: float, run_dir: Path, cache_dir=None,
            span=contextlib.nullcontext()) -> Window:
    """Call ``PDFSession.run`` over the cube until ``seconds`` have passed,
    inside ``span``; the call in flight at the deadline runs to its end, and
    only windows handed back by the deadline count."""
    from repro.api import PDFSession
    from repro.runtime import cluster

    calls_dir = run_dir / "calls"
    shutil.rmtree(calls_dir, ignore_errors=True)
    calls, handed = [], []
    base = cluster.compile_counters()
    with span:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        k = 0
        while time.perf_counter() < deadline:
            out = calls_dir / f"call{k:04d}"
            session = PDFSession(pipeline_spec(cell, st.source_spec, out, cache_dir),
                                 tree=st.tree)

            def on_window(ws, out=out):
                w = ws.window
                handed.append((out, w.slice_i, w.line_start, ws.num_points,
                               time.perf_counter(), ws.num_fitted))

            for _ in session.run(on_window=on_window):
                pass
            calls.append((out, session.report()))
            k += 1
        t_end = time.perf_counter()
    delta = cluster.counters_delta(base)
    counted = sum(h[3] for h in handed if h[4] <= deadline)
    return Window(calls, handed, counted, delta, t0, t_end)


def run_check(cell: Cell, st: Setup, win: Window, seed: int, fit_all: bool,
              control: bool = False) -> dict[str, float]:
    """All numbers of the check: the scan of every handed-back window and
    the sampled comparison with the reference."""
    wl = cell.workload["check"]
    persisted = [h[:4] for h in win.handed_back
                 if check.window_file(h[0], h[1], h[2]).exists()]
    rng = np.random.default_rng([cubemod.rng_seed(seed), 0x5EED])
    sample = check.sample_points(rng, persisted, wl["windows"], wl["points_per_window"])
    answers = check.load_answers(sample)
    numbers = check.reference_numbers(st.gen, cell.config, answers, sample,
                                      fit_all, control=control)
    numbers.update(check.scan([h[:4] for h in win.handed_back]))
    return numbers


def cleanup(run_dir: Path):
    for name in ("calls", "cube", "warm", "trace"):
        shutil.rmtree(run_dir / name, ignore_errors=True)


def emit(result: dict, compared: dict):
    """Numbers compared as the last lines of stderr, then the result line."""
    for k, v in compared.items():
        print(f"[check] {k}={v['value']!r} limit={v['limit']!r}", file=sys.stderr, flush=True)
    result = dict(result)
    result["check"] = compared
    print(json.dumps(result), flush=True)
    return result


class Context:
    """What a per-layer metric reader sees."""

    def __init__(self, cell, window, trace, peaks, chips):
        self.cell, self.window, self.trace = cell, window, trace
        self.peaks, self.chips = peaks, chips
        self.notes: dict = {}


def start_trace(trace_dir: Path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             run_dir: Path, cache_dir=None, require_chip: bool = True) -> dict:
    """One run of one cell; prints the result line and returns it. Raises
    ``ChipMissing``, printing no result, when the chips are not there (the
    cube's generation, started to overlap JAX's start, is stopped first)."""
    import jax

    def log(s):
        print(s, file=sys.stderr, flush=True)

    run_dir = Path(run_dir)
    run_dir.mkdir(parents=True, exist_ok=True)
    job = CubeJob(cell, seed, run_dir)  # overlaps JAX's start
    try:
        dev = device_info(cell.chips, require_chip)
        log(f"[device] platform={dev['platform']} kind={dev['kind']!r} count={dev['count']}")
        peaks = peaks_for(dev["kind"], cell.root) if require_chip else None
    except BaseException:
        job.abort()
        cleanup(run_dir)
        raise
    if cache_dir is not None:
        from repro.runtime import cluster

        cluster.enable_compilation_cache(cache_dir)
    try:
        log(f"[setup] init_s={time.perf_counter() - t_start}")
        st = setup(cell, seed, run_dir, cache_dir, log=log, job=job)
        trace_dir = run_dir / "trace"
        summary = None
        if trace:
            start_trace(trace_dir)
        try:
            span = (jax.profiler.TraceAnnotation("bench.window") if trace
                    else contextlib.nullcontext())
            win = measure(cell, st, seconds, run_dir, cache_dir, span=span)
        finally:
            if trace:
                jax.profiler.stop_trace()
        setup_s = win.t0 - t_start
        log(f"[setup] setup_s={setup_s}")
        peak = memory_peak_bytes()
        log(f"[window] calls={len(win.calls)} windows={len(win.handed_back)} "
            f"counted_points={win.counted_points} elapsed_s={win.t_end - win.t0} "
            f"compile_delta={win.compile_delta} memory_peak_bytes={peak}")
        log("[window] per call (wall, wait, compute, persist): " + " ".join(
            f"({r.wall_seconds:.3f},{r.wait_seconds:.3f},{r.compute_seconds:.3f},"
            f"{r.persist_seconds:.3f})" for _d, r in win.calls))
        if trace:
            from bench import trace as tracemod

            summary = tracemod.summarize(tracemod.find_xplane(trace_dir), cell.chips)
        fit_all = cell.traffic["method"] == "baseline"
        numbers = run_check(cell, st, win, seed, fit_all)
        correct, compared = check.decide(numbers, cell.workload["check"]["limits"])
        info = {k: v for k, v in numbers.items() if k not in compared}
        log(f"[check] not compared: {info}")

        metrics = {}
        if trace:
            ctx = Context(cell, win, summary, peaks, cell.chips)
            for m in cell.per_layer:
                value = metric_reader(cell.root, m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            log(f"[trace] busy_s={summary['busy_s']} window_s={summary['window_s']} "
                f"kernels={ctx.notes}")
        else:
            values = {"points_per_s": win.counted_points / seconds, "setup_s": setup_s}
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        device = dict(dev, memory_peak_bytes=peak)
        if trace:
            device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        failed = int(numbers["bad_windows"])
        result = {"correct": bool(correct), "attempted": len(win.handed_back),
                  "failed": failed, "metrics": metrics, "device": device}
        if trace:
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
        return emit(result, compared)
    finally:
        cleanup(run_dir)

