"""Multi-process cluster execution + cold-start elimination (DESIGN.md §17).

The paper's weak-scaling runs put independent Spark workers on separate
nodes, each computing whole slices of the cube against shared storage. This
module is that topology for the JAX pipeline: N ``launch/run_pdf``
processes, each pinned to one shard of the round-robin slice deal
(``scheduler.assign_slices``), optionally joined into one
``jax.distributed`` world, all persisting to a shared ``out_dir``. There
are **no cross-process collectives** — slices are independently
recomputable partitions (the Random Sample Partition model), so bitwise
identity with the single-process run follows from the staged executor's
per-slice equivalence contract, and process failure is survivable by
construction.

Three seams live here:

* **Placement** (``ExecSpec.placement``): ``apply_placement`` pins the
  process to its shard; ``device_placement`` maps a shard to a local device
  (``SingleDeviceSharding`` through ``StagedExecutor``'s ``sharding=``
  seam); ``init_distributed`` joins the ``jax.distributed`` world.
* **Elasticity** (shrink *and* grow): every worker writes ``alive`` →
  ``done``/``lost`` marker files under ``out_dir/cluster``. Survivors wait
  for every original shard's terminal marker, then re-deal the incomplete
  slices of lost shards over the *done* set (``elastic.plan_redeal``) —
  deterministic across survivors because the healthy set is exactly the
  original shards with ``done`` markers. A join-only worker
  (``process_id >= num_processes``) adds itself via ``plan_redeal``'s
  ``joined`` parameter: it duplicates at worst (identical bytes), and when
  every original shard died it completes the run alone.
* **Cold start**: ``enable_compilation_cache`` points the persistent XLA
  compilation cache at ``JAX_COMPILATION_CACHE_DIR``, else at
  ``ExecSpec.compile_cache_dir``, else at a fixed ``.jax_cache/`` in the
  checkout, so a re-launched spec serves every executable from disk;
  ``compile_counters`` snapshots the process-wide trace/compile/cache
  event counts (``jax.monitoring``) that ``SessionReport`` exposes so
  "zero new compilations" is assertable. A corrupt cache entry is a warned
  miss (JAX recompiles), never a crash.

``python -m repro.runtime.cluster --compare REF OUT`` verifies two persisted
output directories bitwise — the invariant line CI's distributed-smoke job
greps for.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from pathlib import Path
from typing import Callable, Iterator

from repro.runtime import elastic
from repro.runtime.faults import ShardLostError, shard_lost_from
from repro.runtime.scheduler import assign_slices

# -- compile/trace counters (cold-start visibility) ----------------------------

_COUNTS = {
    "traces": 0,
    "compiles": 0,
    "persistent_cache_hits": 0,
    "persistent_cache_misses": 0,
}
_COUNTS_LOCK = threading.Lock()
_LISTENERS_INSTALLED = False

_EVENT_KEYS = {
    "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "persistent_cache_misses",
}
_DURATION_KEYS = {
    "/jax/core/compile/backend_compile_duration": "compiles",
    "/jax/core/compile/jaxpr_trace_duration": "traces",
}


def _on_event(event: str, **kw) -> None:
    key = _EVENT_KEYS.get(event)
    if key is not None:
        with _COUNTS_LOCK:
            _COUNTS[key] += 1


def _on_duration(event: str, duration: float, **kw) -> None:
    key = _DURATION_KEYS.get(event)
    if key is not None:
        with _COUNTS_LOCK:
            _COUNTS[key] += 1


def install_compile_listeners() -> None:
    """Register the ``jax.monitoring`` listeners feeding ``compile_counters``
    (once per process; listeners cannot be unregistered, so the counters are
    process-wide monotonic)."""
    global _LISTENERS_INSTALLED
    if _LISTENERS_INSTALLED:
        return
    from jax._src import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _LISTENERS_INSTALLED = True


def compile_counters() -> dict[str, int]:
    """Snapshot of process-wide XLA activity since the listeners went in:
    ``traces`` (jaxpr traces), ``compiles`` (backend compile calls — these
    fire on persistent-cache hits too, XLA still invokes the compiler entry
    point), and the persistent compilation cache's hit/miss counts. The
    cold-start indicator is ``persistent_cache_misses == 0``: with the
    cache enabled, a miss is exactly "an executable that had to be built
    fresh". ``PDFSession`` snapshots at construction and reports the delta."""
    install_compile_listeners()
    with _COUNTS_LOCK:
        return dict(_COUNTS)


def counters_delta(baseline: dict[str, int]) -> dict[str, int]:
    now = compile_counters()
    return {k: now[k] - baseline.get(k, 0) for k in now}


# -- persistent compilation cache ----------------------------------------------


# The cache directory when neither JAX_COMPILATION_CACHE_DIR nor
# ExecSpec.compile_cache_dir names one: a fixed path inside the checkout
# (gitignored). The path is part of what a later launch must find again, so
# it is never built from a temporary name, a pid or the time.
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache(configured: str | Path | None = None) -> Path:
    """Turn JAX's persistent compilation cache on, and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it from the
    environment, so no directory is set here), else ``configured``
    (``ExecSpec.compile_cache_dir``) as the directory itself, else
    ``DEFAULT_COMPILE_CACHE_DIR``.

    One directory serves every spec: XLA keys each entry by the program,
    device and flags, so identical kernels of two specs share an entry.
    Thresholds are dropped to cache everything (the pipeline's executables
    are small, and re-launch cost is the point) — which also makes a
    persistent-cache miss mean exactly "built fresh". Safe to call
    repeatedly; switching directories resets JAX's in-memory cache handle."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = Path(env or configured or DEFAULT_COMPILE_CACHE_DIR)
    path.mkdir(parents=True, exist_ok=True)
    if not env:
        previous = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", str(path))
        if previous and previous != str(path):
            from jax.experimental.compilation_cache import compilation_cache

            compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# -- placement -----------------------------------------------------------------


def apply_placement(spec):
    """Pin a spec to this process's seat in the cluster: with
    ``placement.num_processes > 1``, ``execution.shards`` becomes the
    process count and ``execution.shard`` this process's id — the same
    per-node single-shard mode ``run_pdf --shard`` always offered, now
    derived from the placement section. Join-only workers
    (``process_id >= num_processes``) get no shard pin (they run nothing
    until redeal). Single-process specs pass through unchanged."""
    pl = spec.execution.placement
    if pl.num_processes <= 1 and pl.process_id is None:
        return spec
    if pl.num_processes > 1 and pl.process_id is None:
        raise ValueError(
            "placement.num_processes > 1 requires placement.process_id: "
            "each worker process must know its seat (launch/cluster.sh "
            "passes --process-id per process)")
    if spec.execution.shards not in (1, pl.num_processes):
        raise ValueError(
            f"execution.shards={spec.execution.shards} conflicts with "
            f"placement.num_processes={pl.num_processes} — leave shards "
            "unset in cluster mode (the placement section owns the deal)")
    shard = pl.process_id if pl.process_id < pl.num_processes else None
    return dataclasses.replace(spec, execution=dataclasses.replace(
        spec.execution, shards=pl.num_processes, shard=shard))


_DISTRIBUTED = {"initialized": False}


def init_distributed(placement) -> bool:
    """Join the ``jax.distributed`` world this placement describes
    (idempotent). Returns True when this process holds a seat — join-only
    workers and single-process runs return False (the world size is fixed
    at initialization, which is exactly why growth goes through the marker
    protocol instead)."""
    if placement.num_processes <= 1 or not placement.distributed:
        return False
    pid = placement.process_id
    if pid is None or pid >= placement.num_processes:
        return False
    if _DISTRIBUTED["initialized"]:
        return True
    import jax

    jax.distributed.initialize(
        coordinator_address=placement.coordinator,
        num_processes=placement.num_processes,
        process_id=pid,
    )
    _DISTRIBUTED["initialized"] = True
    return True


def device_placement(placement, shard: int):
    """The ``jax.sharding.Sharding`` a shard's executor stages onto, or
    None for the backend default. ``shard_devices`` indexes
    ``jax.local_devices()`` round-robin — the per-shard device placement
    seam (``StagedExecutor(sharding=...)``); single-device staging keeps
    results bitwise-identical on any placement."""
    if placement is None or placement.shard_devices is None:
        return None
    import jax

    devices = jax.local_devices()
    idx = placement.shard_devices[shard % len(placement.shard_devices)]
    if idx >= len(devices):
        raise ValueError(
            f"placement.shard_devices asks for local device {idx} but only "
            f"{len(devices)} local device(s) exist")
    return jax.sharding.SingleDeviceSharding(devices[idx])


# -- the marker protocol -------------------------------------------------------

MARKER_DIRNAME = "cluster"
_POLL_S = 0.05


def _marker_dir(out_dir: str | Path) -> Path:
    return Path(out_dir) / MARKER_DIRNAME


def marker_path(out_dir: str | Path, shard: int, state: str) -> Path:
    return _marker_dir(out_dir) / f"shard{shard}.{state}"


def write_marker(out_dir: str | Path, shard: int, state: str,
                 payload: dict | None = None) -> None:
    """Atomically publish a worker state file (tmp + rename, so a peer never
    reads a torn marker)."""
    d = _marker_dir(out_dir)
    d.mkdir(parents=True, exist_ok=True)
    tmp = d / f".shard{shard}.{state}.tmp"
    tmp.write_text(json.dumps({"shard": shard, "pid": os.getpid(),
                               **(payload or {})}))
    tmp.replace(marker_path(out_dir, shard, state))


def wait_for_peers(out_dir: str | Path, placement,
                   my_shard: int) -> tuple[list[int], list[int]]:
    """Block until every original shard has a terminal (done/lost) marker,
    up to ``peer_timeout_s`` — silent peers past the deadline are treated
    as lost. Returns ``(done, lost)`` sorted; ``done`` includes this worker
    when it holds an original seat. Because every survivor waits for the
    same terminal set, all survivors compute the same redeal plan."""
    deadline = time.monotonic() + placement.peer_timeout_s
    peers = [s for s in range(placement.num_processes) if s != my_shard]
    done = {my_shard} if my_shard < placement.num_processes else set()
    lost: set[int] = set()
    while True:
        for s in peers:
            if s in done or s in lost:
                continue
            if marker_path(out_dir, s, "done").exists():
                done.add(s)
            elif marker_path(out_dir, s, "lost").exists():
                lost.add(s)
        if len(done) + len(lost) >= placement.num_processes:
            break
        if time.monotonic() > deadline:
            lost.update(s for s in peers if s not in done)
            break
        time.sleep(_POLL_S)
    return sorted(done), sorted(lost)


def slice_complete(out_dir: str | Path, slice_i: int, lines_per_slice: int,
                   spec_hash: str | None) -> bool:
    """Whether a slice's persisted watermark says it finished under this
    spec — the recovery line the redeal scan uses to compute a dead shard's
    *unfinished* slices. Prefers the watermark's explicit ``complete`` stamp
    (PersistStage writes one when it knows the slice's line count), falling
    back to the line-count comparison for watermarks from older runs."""
    f = Path(out_dir) / f"slice{slice_i}_watermark.json"
    if not f.exists():
        return False
    try:
        info = json.loads(f.read_text())
    except (OSError, ValueError):
        return False  # torn mid-write: treat as incomplete, recompute
    stored = info.get("spec_hash")
    if stored and spec_hash and stored != spec_hash:
        return False
    if "complete" in info:
        return bool(info["complete"])
    return int(info.get("next_line", 0)) >= lines_per_slice


# -- the worker loop -----------------------------------------------------------


def run_worker(session, on_window: Callable | None = None,
               log: Callable[[str], None] | None = None) -> Iterator:
    """One cluster worker's whole life, as a ``SliceResult`` generator:
    run this process's dealt slices, publish the terminal marker, then (with
    ``placement.redeal``) wait for peers and pick up this worker's share of
    any dead peer's unfinished slices (``resume=True`` — windows the dead
    worker persisted are skipped, recomputed windows are bitwise-identical).
    A worker whose own shard dies (``ShardLostError``) publishes ``lost``
    and stops — its recovery belongs to the survivors. Join-only workers
    skip the initial run and enter directly at the redeal step via
    ``plan_redeal(joined=...)``."""
    spec = session.spec
    pl = spec.execution.placement
    out_dir = spec.execution.out_dir
    if out_dir is None:
        raise ValueError("cluster workers require execution.out_dir")
    emit = log if log is not None else (lambda s: None)
    my = pl.process_id if pl.process_id is not None else (
        spec.execution.shard or 0)
    joiner = my >= pl.num_processes
    write_marker(out_dir, my, "alive", {"join": joiner})
    try:
        if not joiner:
            yield from session.run(on_window=on_window)
    except Exception as e:
        if shard_lost_from(e) is None:
            write_marker(out_dir, my, "lost", {"error": repr(e)})
            raise
        write_marker(out_dir, my, "lost", {"injected": True})
        emit(f"[cluster] shard {my} lost mid-run — survivors will redeal")
        return
    write_marker(out_dir, my, "done", {})
    if not pl.redeal or pl.num_processes <= 1:
        return
    done, lost = wait_for_peers(out_dir, pl, my)
    if not lost:
        return
    resolved = session.resolve_slices(None)
    assignment = {a.shard: a.slices
                  for a in assign_slices(resolved, pl.num_processes)}
    lines = session.geometry.lines_per_slice
    pending = [s for sh in lost for s in assignment.get(sh, ())
               if not slice_complete(out_dir, s, lines, session.spec_hash)]
    if not pending:
        return
    session.shards_lost = tuple(lost)
    plan = elastic.plan_redeal(pending, done, lost,
                               joined=(my,) if joiner else ())
    mine = plan.slices_for(my)
    if not mine:
        return
    emit(f"[cluster] shard {my} redealing slices {list(mine)} from lost "
         f"shard(s) {lost}")
    yield from session.run_local(mine, shard=my, resume=True,
                                 on_window=on_window)


# -- bitwise output verification (the distributed-smoke invariant) -------------


def verify_outputs(ref_dir: str | Path, out_dir: str | Path) -> tuple[int, int]:
    """Assert two persisted output directories hold bitwise-identical window
    results. Compares the full ``slice*_window_*.npz`` sets — same file
    names, same array keys, ``np.array_equal`` on every array (the files'
    raw zip bytes differ by timestamps; the *arrays* are the contract).
    Returns ``(windows, arrays)`` compared; raises ``AssertionError`` on
    any divergence."""
    import numpy as np

    ref_dir, out_dir = Path(ref_dir), Path(out_dir)
    ref_files = sorted(p.name for p in ref_dir.glob("slice*_window_*.npz"))
    out_files = sorted(p.name for p in out_dir.glob("slice*_window_*.npz"))
    if not ref_files:
        raise AssertionError(f"no persisted windows under {ref_dir}")
    if ref_files != out_files:
        raise AssertionError(
            f"window sets differ: only-ref={sorted(set(ref_files) - set(out_files))} "
            f"only-out={sorted(set(out_files) - set(ref_files))}")
    arrays = 0
    for name in ref_files:
        with np.load(ref_dir / name, allow_pickle=False) as a, \
                np.load(out_dir / name, allow_pickle=False) as b:
            if sorted(a.files) != sorted(b.files):
                raise AssertionError(
                    f"{name}: array keys differ ({sorted(a.files)} vs "
                    f"{sorted(b.files)})")
            for k in a.files:
                if not np.array_equal(a[k], b[k]):
                    raise AssertionError(
                        f"{name}[{k}]: arrays differ (not bitwise-identical)")
                arrays += 1
    return len(ref_files), arrays


def main(argv: list[str] | None = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.runtime.cluster",
        description="cluster tooling: bitwise output verification")
    ap.add_argument("--compare", nargs=2, metavar=("REF", "OUT"),
                    help="assert two persisted out_dirs are bitwise-identical")
    args = ap.parse_args(argv)
    if not args.compare:
        ap.error("nothing to do — pass --compare REF OUT")
    windows, arrays = verify_outputs(*args.compare)
    print(f"[cluster] bitwise-identical windows={windows} arrays={arrays}")


if __name__ == "__main__":
    main()
