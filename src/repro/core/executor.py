"""Staged PDF executor: load / compute / persist as decoupled stages.

The paper's speedup is not only the per-point kernels — Spark overlaps data
loading with computation and spreads slices across the cluster. This module
is that layer for the JAX reproduction:

  load stage     WindowPrefetcher (data/loader.py) pulls WorkUnits off the
                 plan in order, loading window *k+1* from the data source
                 and staging it host->device while the device is still
                 fitting window *k* (device work, including the moments
                 kernel, stays on the compute stage — see _StagedWindow).
                 A source that reads into ``out`` (FileCubeSource) fills a
                 host buffer recycled from the executor's free-list; the
                 load waits for that window's transfer to land before the
                 buffer goes back. A window of two ``_MIN_PIECE_BYTES`` or
                 more bound for one device, from any source, is staged in
                 pieces of whole lines: each piece's transfer is enqueued
                 before the next piece is read, so it runs under that read,
                 and the pieces are assembled into the window on the
                 device (a concatenate, bitwise the one-piece window)
                 before the buffer goes back. Smaller windows, a sharding
                 over several devices and ``run_window`` stage in one piece.
  compute stage  the main thread: Select (grouping / reuse / ML dispatch)
                 on host + batched ComputePDF&Error on device — identical
                 operations, in identical order, to the old serial loop, so
                 results are bitwise-equal with prefetch on or off.
  persist stage  a single writer thread appends per-window ``.npz``
                 watermarks off the critical path; submission order is
                 preserved so the watermark never runs ahead of a persisted
                 window, and ``close()`` flushes before the executor
                 returns (or re-raises), keeping the serial path's
                 crash-consistency guarantee.

Per-stage heartbeats feed ``runtime.monitor.StepMonitor`` instances (one per
stage), so straggler flagging and stage medians come for free; the
``ExecutorReport`` summarizes how much load time was hidden behind compute
(``wait_seconds`` is the only part of the load the device actually blocked
on).

Each step is a named span (``runtime.monitor.SpanRecorder``, one per
executor) carrying the window's ``slice`` and ``line``: ``pdf.load.read``
and ``pdf.load.h2d`` on the prefetch thread, ``pdf.persist.write`` on the
writer thread, and on the main thread ``pdf.slice.open``,
``pdf.load.first`` (the wait for a run's first window, which no compute
overlaps), ``pdf.load.wait`` (the wait for each later window and for the
stream's end), ``pdf.moments``, ``pdf.select``, ``pdf.fit.launch``,
``pdf.fit.wait``, ``pdf.handoff`` and ``pdf.slice.drain``. Both waits count
in ``wait_seconds``. Main-thread spans never nest (with prefetch off, the
load spans run on the main thread, inside the wait). The heartbeats and
the report's stage totals take their times from the spans' own clock
reads; ``ExecutorReport.spans`` and ``counters`` carry the run's span
totals and work counts (``windows``, ``points``, ``fit_rows``,
``fit_rows_padded``, ``groups``, ``bytes_read``, ``bytes_h2d``,
``read_recycled``, ``load_pieces``: the pieces each window was staged in,
1 for a window staged whole). In a window staged in pieces,
``pdf.load.read`` holds the piece reads and their transfers' enqueues, and
``pdf.load.h2d`` only what the reads did not hide: the last piece's
transfer and the assembly, to the assembled window's landing.

``PDFComputer`` (pipeline.py) is a thin facade over this executor; the
multi-slice entry point is ``run`` on a ``regions.Plan``, which
``runtime.scheduler`` uses for per-node slice assignment.
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import json
import queue
import threading
import time
import warnings
from concurrent import futures
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributions as dists
from repro.core import fitting
from repro.core import grouping as grp
from repro.core import ml_predict as mlp
from repro.core import regions
from repro.core.reuse import ReuseCache
from repro.data.loader import PrefetchError, WindowPrefetcher
from repro.runtime.faults import ShardLostError, is_transient
from repro.runtime.monitor import SpanRecorder, StepMonitor, StragglerPolicy

METHODS = (
    "baseline", "grouping", "reuse", "ml", "grouping_ml", "reuse_ml",
    # §5.4 / Algorithm 5: estimate slice features from a sampled fraction of
    # points — tree classification only, no Eq.-5 fitting. A first-class
    # registry entry so the sampling figures run through the same staged
    # executor as every other method (it used to be benchmark-side glue).
    "sampling",
)

# Point samplers for method='sampling' (§5.4: random is the paper's
# recommendation; k-means "double sampling" wins at tiny rates).
SAMPLERS = ("random", "kmeans")

# Where the Select step's dedup runs (DESIGN.md §6): 'host' bounces the
# window's quantized keys through np.unique + a padded representative
# re-dispatch; 'device' keeps quantize -> group_device -> representative
# gather -> fit -> scatter on the accelerator (one jitted launch for the
# grouping methods; reuse keeps its host cache but deduplicates on device).
# Both produce bitwise-identical per-point results (tests/test_select_backends)
# where float64 is IEEE; 'device' refuses to run on a TPU.
SELECT_BACKENDS = ("host", "device")
# Backends whose float64 is emulated, not IEEE. On a TPU v5e, 20,000 random
# (mean, var) per case at magnitudes 1e-3..1e6 and tolerances 1e-6..1e-2
# got device keys one unit away from the host keys for 1.7-19% of points in
# 16 of 18 cases (ROADMAP design item 1): device Select could split or
# merge groups differently from host Select.
DEVICE_SELECT_REFUSED_PLATFORMS = ("tpu",)

# Tree features: scale-invariant moments (cv = sigma/|mu|, skew, excess
# kurtosis). The paper uses (mu, sigma) and notes higher normalized moments
# "may take additional time" — our fused moments kernel computes them in the
# same pass, so they are free; scale-invariance makes the classifier
# transfer across slices whose value scales differ (DESIGN.md §8).
TREE_FEATURES = ("cv", "skew", "kurt")


def _quiet_donation(f):
    """The fit executables donate their (P, n) window buffer (memory headroom
    on real accelerators: the staged window is dead once consumed). None of
    the small fit outputs can alias a (P, n) buffer, so XLA warns the
    donation went unused on backends where it finds no other use — expected,
    not actionable. Suppressed per-call so importers' own warning state is
    untouched (the compute stage is single-threaded)."""

    @functools.wraps(f)
    def wrapped(*args):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            return f(*args)

    return wrapped


def tree_features(moments: dists.Moments):
    cv = moments.std / jnp.maximum(jnp.abs(moments.mean), 1e-12)
    return jnp.stack([cv, moments.skew, moments.kurt], axis=-1)  # repro: allow[SHAPE]: fixed (P, 3) feature triple inside every executable — not a batch-shape seam


def tree_features_np(mean, std, skew, kurt):
    cv = std / np.maximum(np.abs(mean), 1e-12)
    return np.stack([cv, skew, kurt], axis=-1).astype(np.float32)


@dataclass(frozen=True)
class PDFConfig:
    types: tuple[str, ...] = dists.TYPES_4
    num_bins: int = 64
    window_lines: int = 25
    method: str = "baseline"
    mode: str = "fused"  # 'faithful' reproduces the paper's per-type pass cost
    group_tol: float = grp.DEFAULT_TOL
    rep_bucket: int = 256  # padding bucket for representative batches
    error_bound: float | None = None  # the paper's bounded-error constraint
    # Device-work implementation (fitting.FIT_BACKENDS): 'reference' (jnp
    # chain), 'kernels' (Pallas moments+hist, chained), 'fused' (the
    # single-launch kernels/fitpdf path — the default hot path).
    fit_backend: str = "fused"
    # Where Select's dedup runs (SELECT_BACKENDS). 'host' stays the default:
    # on small CPU devices np.unique beats the device sort; 'device' removes
    # the per-window key D2H + rep-index H2D bounce entirely (the win on real
    # accelerators — see the kernel/select_* BENCH rows).
    select_backend: str = "host"
    # method='sampling' (§5.4): fraction of window points classified, which
    # sampler draws them, and the Lloyd iteration count for 'kmeans'. The
    # per-window draw is seeded from (sample_seed, slice, line), so results
    # are independent of window execution order and survive resume.
    sample_frac: float = 0.1
    sampler: str = "random"
    kmeans_iters: int = 10
    sample_seed: int = 0

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f"method must be one of {METHODS}, got {self.method!r}")
        if self.num_bins < 2:
            raise ValueError(f"num_bins must be >= 2, got {self.num_bins}")
        if self.window_lines < 1:
            raise ValueError(f"window_lines must be >= 1, got {self.window_lines}")
        if self.error_bound is not None and not self.error_bound > 0:
            # error_bound <= 0 used to sail through construction and report
            # error_bound_satisfied=False at the end of a full run
            raise ValueError(
                f"error_bound must be > 0 (or None), got {self.error_bound}")
        if not 0 < self.sample_frac <= 1:
            raise ValueError(f"sample_frac must be in (0, 1], got {self.sample_frac}")
        if self.sampler not in SAMPLERS:
            raise ValueError(f"sampler must be one of {SAMPLERS}, got {self.sampler!r}")
        if self.kmeans_iters < 1:
            raise ValueError(f"kmeans_iters must be >= 1, got {self.kmeans_iters}")
        if self.fit_backend not in fitting.FIT_BACKENDS:
            raise ValueError(
                f"fit_backend must be one of {fitting.FIT_BACKENDS}, "
                f"got {self.fit_backend!r}"
            )
        if self.select_backend not in SELECT_BACKENDS:
            raise ValueError(
                f"select_backend must be one of {SELECT_BACKENDS}, "
                f"got {self.select_backend!r}"
            )
        if self.rep_bucket < 1:
            # padded_size(g, 0) would spin forever (0 * 2 == 0), and the
            # bucket is now CLI-exposed (--rep-bucket)
            raise ValueError(f"rep_bucket must be >= 1, got {self.rep_bucket}")


@dataclass(frozen=True)
class ExecutorConfig:
    """Staging + fault-tolerance knobs; ``prefetch=False,
    async_persist=False`` reproduces the pre-executor strictly serial loop
    (the reference path for equivalence tests and overlap benchmarks).

    None of these change per-point results — the bitwise-equivalence
    contract: a retried, speculated, or re-dealt work unit recomputes the
    exact bytes the first attempt would have produced (loads are
    deterministic, fits are row-pure), which is precisely what makes
    first-result-wins and re-dealing safe (DESIGN.md §14)."""

    prefetch: bool = True
    prefetch_depth: int = 2  # how many windows the load stage may run ahead
    async_persist: bool = True
    # Work-unit retry: how many *re*-attempts a transiently failing unit
    # gets (so max_retries + 1 attempts total) before it is quarantined
    # (degraded_mode=True) or the run aborts (False). Backoff is
    # exponential (retry_backoff_s * 2^attempt) with a deterministic
    # per-(unit, attempt) jitter.
    max_retries: int = 2
    retry_backoff_s: float = 0.05
    # Straggler speculation: when a window load exceeds
    # max(threshold x trailing-median, straggler_grace_s), re-dispatch an
    # identical load and take whichever finishes first.
    speculate: bool = True
    straggler_grace_s: float = 1.0
    # Degraded completion: quarantine units that exhaust their retries
    # (type_idx = -1, failed-unit manifest next to the watermark) instead
    # of aborting the run.
    degraded_mode: bool = True

    def __post_init__(self):
        if self.prefetch_depth < 1:
            raise ValueError(f"prefetch_depth must be >= 1, got {self.prefetch_depth}")
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if self.retry_backoff_s < 0:
            raise ValueError(
                f"retry_backoff_s must be >= 0, got {self.retry_backoff_s}")
        if self.straggler_grace_s < 0:
            raise ValueError(
                f"straggler_grace_s must be >= 0, got {self.straggler_grace_s}")


class WindowStats(NamedTuple):
    window: regions.Window
    num_points: int
    num_fitted: int  # points actually sent through ComputePDF&Error
    load_seconds: float
    compute_seconds: float
    cache_hits: int
    wait_seconds: float = 0.0  # compute stage blocked waiting for this window


@dataclass
class SliceResult:
    type_idx: np.ndarray  # (P,) int32
    params: np.ndarray  # (P, 3)
    error: np.ndarray  # (P,)
    mean: np.ndarray  # (P,)
    std: np.ndarray  # (P,)
    skew: np.ndarray  # (P,)  (normalized 3rd moment — paper footnote 1)
    kurt: np.ndarray  # (P,)  (excess kurtosis)
    avg_error: float  # Eq. 6
    stats: list[WindowStats] = field(default_factory=list)
    error_bound_satisfied: bool | None = None
    slice_i: int | None = None
    # Provenance: content hash of the PipelineSpec that produced this result
    # (api/spec.py); also stamped into persisted .npz files and watermarks.
    spec_hash: str | None = None
    # True when this result was served from a spec-hash-keyed ResultCache
    # (api/cache.py) instead of being computed; cached results are bitwise
    # identical to computed ones but carry no window stats.
    cached: bool = False
    # Fault-tolerance bookkeeping (DESIGN.md §14): transient re-attempts,
    # speculative re-dispatches, and the quarantined windows of a degraded
    # run — each a dict with unit_id/line_start/line_end/attempts/error,
    # mirrored in the slice's failed-unit manifest on disk. A quarantined
    # window's points carry type_idx = -1 and zero params/moments.
    retries: int = 0
    speculations: int = 0
    quarantined: tuple = ()

    @property
    def degraded(self) -> bool:
        """True when any work unit was quarantined — the result is complete
        for every other window but NOT cacheable as the slice's answer."""
        return len(self.quarantined) > 0

    def features(self, types) -> "object":
        """§5.4 slice features (SliceFeatures) from this result: average
        mean/std and type percentages over the *classified* points — all of
        them for the fitting methods, the sampled subset for
        ``method='sampling'`` (unsampled points carry ``type_idx == -1``)."""
        from repro.core.sampling import SliceFeatures

        m = self.type_idx >= 0
        n = int(m.sum())
        pct = (np.bincount(self.type_idx[m], minlength=len(types))
               .astype(np.float64) / max(n, 1))
        return SliceFeatures(
            float(self.mean[m].mean()) if n else 0.0,
            float(self.std[m].mean()) if n else 0.0,
            pct, n,
        )

    @property
    def total_load_seconds(self) -> float:
        return sum(s.load_seconds for s in self.stats)

    @property
    def total_compute_seconds(self) -> float:
        return sum(s.compute_seconds for s in self.stats)

    @property
    def total_wait_seconds(self) -> float:
        return sum(s.wait_seconds for s in self.stats)


@dataclass(frozen=True)
class ExecutorReport:
    """Per-stage totals for one ``run``. ``wait_seconds`` is the time the
    compute stage spent blocked on the load stage — with prefetch it should
    be a small fraction of ``load_seconds`` (the rest was hidden behind
    compute); serially the two are equal by construction."""

    wall_seconds: float
    units: int
    load_seconds: float
    wait_seconds: float
    compute_seconds: float
    persist_seconds: float
    # Fault-tolerance totals across the run's slices (DESIGN.md §14).
    retries: int = 0
    speculations: int = 0
    speculation_wins: int = 0
    quarantined: int = 0
    # The run's span totals {name: (seconds, count)} and work counters
    # {name: count}, from the executor's SpanRecorder.
    spans: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)

    @property
    def load_hidden_seconds(self) -> float:
        return max(0.0, self.load_seconds - self.wait_seconds)

    @property
    def load_hidden_fraction(self) -> float:
        return self.load_hidden_seconds / self.load_seconds if self.load_seconds > 0 else 0.0


@functools.lru_cache(maxsize=64)
def _jitted_fns(types: tuple, num_bins: int, mode: str, fit_backend: str):
    """Module-level jit cache: every executor with the same (types, bins,
    mode, backend) shares compiled executables — windows, slices and method
    variants reuse them instead of recompiling per instance.

    The fit entry points donate their window buffer: the prefetcher's staged
    array (or the grouping path's gathered representative batch) is dead
    once the fit has consumed it, so XLA reuses it in place instead of
    copying (moments_f runs first on the same buffer and must not donate).
    """
    backend = fitting.get_fit_backend(fit_backend, num_bins)

    @jax.jit
    def moments_f(values):
        return backend.moments(values)

    @_quiet_donation
    @functools.partial(jax.jit, donate_argnums=(0,))
    def fit_all_f(values, moments):
        r = backend.fit_all(values, moments, types, num_bins, mode)
        return r.type_idx, r.params, r.error

    @_quiet_donation
    @functools.partial(jax.jit, donate_argnums=(0,))
    def fit_pred_f(values, moments, tree_arrays):
        # Tree features + the fixed-depth descent live inside the executable:
        # the predict step is ~15 eager dispatches per window otherwise.
        pred = mlp.predict(tree_arrays, tree_features(moments))
        r = backend.fit_predicted(values, moments, pred, types, num_bins)
        return r.type_idx, r.params, r.error

    @jax.jit
    def gather_f(values, moments, idx):
        # One executable for the grouping/reuse representative gather: the
        # values rows and all six moment fields in a single dispatch (the
        # per-field np round-trips used to dominate small grouped windows).
        return fitting.gather_rows(values, moments, idx)

    return moments_f, fit_all_f, fit_pred_f, gather_f


class _SelectFns(NamedTuple):
    """Jitted entry points of the device Select path (select_backend='device').

    ``probe`` is the only per-window sync: it returns the device partition
    (rep_for_point, is_rep stay on device) plus the scalar group count
    the host needs to pick a static padded batch size. ``select_fit_all`` /
    ``select_fit_pred`` then run gather -> fit -> scatter in one launch;
    ``compact`` serves the reuse methods, which keep their host cache but
    never bounce the full (P,) keys through np.unique."""

    probe: Callable
    select_fit_all: Callable
    select_fit_pred: Callable
    compact: Callable


@functools.lru_cache(maxsize=64)
def _jitted_select_fns(
    types: tuple, num_bins: int, mode: str, fit_backend: str, group_tol: float
) -> _SelectFns:
    """Device-side Select executables (ROADMAP 'grouping-aware fused
    dispatch'): quantize -> group_device -> representative gather -> fit ->
    scatter without the host dedup bounce. Safe to build on the now-exact
    hi/lo keys: the device partition is bit-identical to the host f64 one,
    so per-point results match the host Select path bitwise (per-row fit
    determinism: every backend's fit is row-independent, so batch order and
    padding rows cannot change a representative's result)."""
    backend = fitting.get_fit_backend(fit_backend, num_bins)

    @jax.jit
    def probe_f(moments):
        # The keys themselves are NOT an output: the grouping methods never
        # consume them, and re-deriving them in compact_f (elementwise, no
        # sort) is cheaper than committing a (P, 4) buffer every window.
        keys = grp.quantize_keys_from_var(moments.mean, moments.var, group_tol)
        g = grp.group_device(keys)
        return g.num_groups, g.rep_for_point, g.is_rep

    @_quiet_donation
    @functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0,))
    def select_fit_all_f(values, moments, rep_for_point, is_rep, padded_g):
        gather_idx, point_slot = grp.compact_representatives(
            rep_for_point, is_rep, padded_g
        )
        r = fitting.fit_all_rows(
            backend, values, moments, gather_idx, types, num_bins, mode
        )
        return (
            grp.scatter_group_results(r.type_idx, point_slot),
            grp.scatter_group_results(r.params, point_slot),
            grp.scatter_group_results(r.error, point_slot),
        )

    @_quiet_donation
    @functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0,))
    def select_fit_pred_f(values, moments, rep_for_point, is_rep, tree_arrays, padded_g):
        gather_idx, point_slot = grp.compact_representatives(
            rep_for_point, is_rep, padded_g
        )
        sub_vals, sub_mom = fitting.gather_rows(values, moments, gather_idx)
        pred = mlp.predict(tree_arrays, tree_features(sub_mom))
        r = backend.fit_predicted(sub_vals, sub_mom, pred, types, num_bins)
        return (
            grp.scatter_group_results(r.type_idx, point_slot),
            grp.scatter_group_results(r.params, point_slot),
            grp.scatter_group_results(r.error, point_slot),
        )

    @functools.partial(jax.jit, static_argnums=(3,))
    def compact_f(moments, rep_for_point, is_rep, padded_g):
        keys = grp.quantize_keys_from_var(moments.mean, moments.var, group_tol)
        gather_idx, point_slot = grp.compact_representatives(
            rep_for_point, is_rep, padded_g
        )
        return gather_idx, keys[gather_idx], point_slot

    return _SelectFns(probe_f, select_fit_all_f, select_fit_pred_f, compact_f)


class _StagedWindow(NamedTuple):
    """Load-stage output: device-resident values, ready for the moments
    kernel. Moments are deliberately NOT dispatched here: launching them
    from the prefetch thread makes two XLA computations contend for the
    device (a measurable slowdown on small CPU devices), while the kernel
    itself is cheap relative to the fit — so it stays on the compute
    stage's critical path, like every other device op."""

    unit: regions.WorkUnit
    values: jax.Array
    load_seconds: float


class _FailedUnit(NamedTuple):
    """Load/compute-stage output for a unit that exhausted its retries in
    degraded mode: flows down the same stream as ``_StagedWindow`` (raising
    from the prefetch thread would kill the whole stream) and is quarantined
    by the run loop instead of computed."""

    unit: regions.WorkUnit
    error: str
    attempts: int


# Host read buffers an executor keeps for reuse: one is in flight per
# loading thread (the prefetcher, or a speculation pair).
_FREE_BUFFERS = 4

# The least bytes of a window's piece when the load stages it in pieces of
# whole lines (_load_unit): each piece's transfer runs while the next piece
# is read, so only the last piece's transfer and the on-device assembly
# stay in the load's path. A window under twice this takes one piece, so
# Set1's 25 MB windows and all one-line tails stage whole. On a v5e host a
# 251 MB Set3 window took 88.5 ms to read and land whole, 70-72 ms in 3 to
# 7 pieces; the unhidden tail fell from 11.1 ms (3) to 6.6 ms (7, this
# size: pieces of ~36 MB). The enqueue itself costs ~0.4 ms (PERF.md §6).
_MIN_PIECE_BYTES = 32 << 20


@jax.jit
def _assemble(*pieces: jax.Array) -> jax.Array:
    """A window's line pieces, back in one ``(points, observations)`` array
    on their device: data movement only, so bitwise the window staged
    whole."""
    return jnp.concatenate(pieces, axis=0)  # repro: allow[SHAPE]: rebuilds the window's own (P, n) shape from its line pieces — data movement, no arithmetic to drift


def _piece_lines(num_lines: int, nbytes: int) -> list[int]:
    """Line bounds of the pieces a window of ``nbytes`` is staged in: as
    many as whole ``_MIN_PIECE_BYTES`` fit, at most one a line, at least
    one."""
    k = max(1, min(nbytes // _MIN_PIECE_BYTES, num_lines))
    return [num_lines * i // k for i in range(k + 1)]


def _takes_out(source) -> bool:
    """Whether ``source.load_window`` reads into a caller's ``out`` (an
    executor may be built without a source, to run none)."""
    load = getattr(source, "load_window", None)
    return load is not None and "out" in inspect.signature(load).parameters


def _ids(w: regions.Window) -> dict:
    """A window's span arguments: its slice and first line, which link the
    window's spans across the prefetch, main and writer threads."""
    return {"slice": w.slice_i, "line": w.line_start}


def _moments_np(moments) -> tuple:
    """Host (mean, std, skew, kurt) of a window's device moments."""
    return (np.asarray(moments[0]),
            np.sqrt(np.maximum(np.asarray(moments[1]), 0)),
            np.asarray(moments[2]), np.asarray(moments[3]))


def _errstr(e: BaseException) -> str:
    return f"{type(e).__name__}: {e}"


# The per-point result arrays of a SliceResult, in persisted/cached order —
# the one canonical list (persist stage, ResultCache, benchmarks and the
# bitwise-equality tests all import it; a new field added here is
# automatically persisted, cached, and covered).
RESULT_FIELDS = ("type_idx", "params", "error", "mean", "std", "skew", "kurt")
_FIELDS = RESULT_FIELDS


class WindowResult(NamedTuple):
    """Per-point results of ONE window — the unit the serving layer
    caches, scatters into answers, and assembles into ``SliceResult``s.
    Field order after ``window`` matches ``RESULT_FIELDS``."""

    window: regions.Window
    type_idx: np.ndarray  # (P,) int32
    params: np.ndarray  # (P, 3) float32
    error: np.ndarray  # (P,)
    mean: np.ndarray  # (P,)
    std: np.ndarray  # (P,)
    skew: np.ndarray  # (P,)
    kurt: np.ndarray  # (P,)

    def arrays(self) -> dict[str, np.ndarray]:
        return {name: getattr(self, name) for name in _FIELDS}


class PersistStage:
    """Writes per-window ``.npz`` + watermark, optionally off-thread.

    One writer thread drains a FIFO queue, so windows of a slice persist in
    submission order and the watermark (``next_line``) is only advanced
    after its window file is durable — exactly the serial path's restart
    contract. ``flush()`` blocks until everything submitted is written;
    the executor flushes before returning *and* before propagating any
    compute-stage exception, so a crash loses at most the in-flight window.
    """

    def __init__(self, out_dir: str | Path | None, async_writes: bool = True,
                 monitor: StepMonitor | None = None,
                 spec_hash: str | None = None,
                 injector=None,
                 total_lines: int | None = None,
                 spans: SpanRecorder | None = None):
        self.out_dir = Path(out_dir) if out_dir else None
        self.monitor = monitor
        self.spans = spans if spans is not None else SpanRecorder()
        self.spec_hash = spec_hash  # stamped into every .npz + watermark
        # Lines per slice, when the caller knows it: lets the watermark
        # carry an explicit ``complete`` stamp (the cluster redeal scan's
        # recovery line) instead of readers re-deriving it from geometry.
        self.total_lines = total_lines
        self.injector = injector  # faults.FaultInjector (on_persist hook)
        self.seconds = 0.0
        self.writes = 0
        self.retries = 0  # transient write failures absorbed in _write
        self._error: BaseException | None = None
        self._async = bool(async_writes and self.out_dir is not None)
        if self._async:
            self._q: queue.Queue = queue.Queue()
            self._thread = threading.Thread(
                target=self._loop, name="window-persist", daemon=True
            )
            self._thread.start()

    # -- submission -----------------------------------------------------------

    def submit(self, slice_i: int, w: regions.Window, arrays: dict[str, np.ndarray]):
        """``arrays`` maps _FIELDS names to the window's result views; the
        views stay valid because windows are disjoint and the output buffers
        outlive the stage."""
        if self.out_dir is None:
            return
        if self._async:
            self._q.put((slice_i, w, arrays))
        else:
            self._write(slice_i, w, arrays)

    def _loop(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                if self._error is None:
                    self._write(*item)
            except BaseException as e:  # repro: allow[ERR]: parked — flush()/raise_if_failed re-raise on the main thread
                self._error = e
            finally:
                self._q.task_done()

    def _write(self, slice_i: int, w: regions.Window, arrays: dict[str, np.ndarray]):
        uid = f"persist:s{slice_i}/l{w.line_start:05d}"
        with self.spans.span("pdf.persist.write", slice=slice_i,
                             line=w.line_start) as sp:
            if self.monitor is not None:
                self.monitor.start(uid, now=sp.start)
            try:
                # Transient write failures (an NFS hiccup mid-savez, or the
                # injector's persist_error) get two quiet re-attempts — a
                # partially-written .npz is simply overwritten, and the
                # watermark only advances after a successful write.
                for attempt in range(3):
                    try:
                        if self.injector is not None:
                            self.injector.on_persist(slice_i, w.line_start)
                        self._write_once(slice_i, w, arrays)
                        break
                    except OSError:
                        if attempt == 2:
                            raise
                        self.retries += 1
                        time.sleep(0.01 * (attempt + 1))
            except BaseException:
                if self.monitor is not None:
                    self.monitor.abandon(uid)
                raise
        if self.monitor is not None:
            self.monitor.finish(uid, now=sp.end)
        self.seconds += sp.seconds
        self.writes += 1

    def _write_once(self, slice_i: int, w: regions.Window,
                    arrays: dict[str, np.ndarray]):
        self.out_dir.mkdir(parents=True, exist_ok=True)
        extra = {"spec_hash": self.spec_hash} if self.spec_hash else {}
        np.savez(
            self.out_dir / f"slice{slice_i}_window_{w.line_start:05d}.npz",
            line_start=w.line_start, line_end=w.line_end, **extra, **arrays,
        )
        mark: dict = {"next_line": int(w.line_end), **extra}
        if self.total_lines is not None:
            mark["complete"] = int(w.line_end) >= self.total_lines
        (self.out_dir / f"slice{slice_i}_watermark.json").write_text(
            json.dumps(mark)
        )

    # -- lifecycle ------------------------------------------------------------

    def flush(self):
        if self._async:
            self._q.join()

    def raise_if_failed(self):
        if self._error is not None:
            raise RuntimeError("persist stage failed") from self._error

    def close(self):
        """Flush pending writes and stop the writer; never raises (call
        ``raise_if_failed`` on the success path)."""
        if self._async and self._thread.is_alive():
            self._q.put(None)
            self._q.join()
            self._thread.join(timeout=5.0)

    # -- watermark / restore (resume) -----------------------------------------

    def watermark_info(self, slice_i: int) -> dict:
        if self.out_dir is None:
            return {"next_line": 0}
        f = self.out_dir / f"slice{slice_i}_watermark.json"
        if not f.exists():
            return {"next_line": 0}
        return json.loads(f.read_text())

    def watermark(self, slice_i: int) -> int:
        return int(self.watermark_info(slice_i)["next_line"])

    def check_resume_hash(self, slice_i: int, info: dict):
        """Resume-mismatch detection: a watermark written under a different
        spec hash describes a *different computation* (other tolerance,
        candidate set, source seed...) — silently mixing its windows into
        this run would corrupt the output, so refuse."""
        stored = info.get("spec_hash")
        if stored and self.spec_hash and stored != self.spec_hash:
            raise ValueError(
                f"resume mismatch for slice {slice_i}: watermark in "
                f"{self.out_dir} was written by spec {stored}, this run is "
                f"spec {self.spec_hash} — point --out-dir elsewhere or "
                "re-run without resume")

    def restore_windows(self, slice_i: int, upto_line: int, ppl: int,
                        outs: dict[str, np.ndarray]):
        for f in sorted(self.out_dir.glob(f"slice{slice_i}_window_*.npz")):
            z = np.load(f)
            if int(z["line_end"]) <= upto_line:
                lo, hi = int(z["line_start"]) * ppl, int(z["line_end"]) * ppl
                for name in _FIELDS:
                    outs[name][lo:hi] = z[name]

    # -- degraded mode: the failed-unit manifest -------------------------------

    def failed_manifest_path(self, slice_i: int) -> Path:
        return self.out_dir / f"slice{slice_i}_failed_units.json"

    def write_failed_manifest(self, slice_i: int, entries: list[dict]):
        """Record a degraded slice's quarantined units next to its watermark
        — the completion contract of degraded mode (DESIGN.md §14): the run
        *finished*, and this file says exactly which windows it finished
        without. An empty entry list deletes the manifest (the slice was
        repaired, e.g. by a resume that re-ran the quarantined units)."""
        if self.out_dir is None:
            return
        f = self.failed_manifest_path(slice_i)
        if not entries:
            f.unlink(missing_ok=True)
            return
        self.out_dir.mkdir(parents=True, exist_ok=True)
        f.write_text(json.dumps(
            {"spec_hash": self.spec_hash, "slice": slice_i, "failed": entries},
            indent=1,
        ))

    def failed_lines(self, slice_i: int) -> set[int]:
        """line_start of every quarantined unit recorded for the slice —
        resume re-runs these even below the watermark (their .npz was never
        written, so the watermark alone cannot see the hole)."""
        if self.out_dir is None:
            return set()
        f = self.failed_manifest_path(slice_i)
        if not f.exists():
            return set()
        return {int(e["line_start"])
                for e in json.loads(f.read_text()).get("failed", ())}


class StagedExecutor:
    """Drives Algorithms 1-2 over a Plan of (slice, window) work units.

    ``data_source`` must expose ``geometry: regions.CubeGeometry`` and
    ``load_window(window) -> np.ndarray (num_points, n_obs) float32``.
    The reuse cache lives on the executor, so windows — and consecutive
    slices of a multi-slice plan — share it exactly as consecutive
    ``run_slice`` calls on one ``PDFComputer`` always have.
    """

    def __init__(
        self,
        config: PDFConfig,
        data_source,
        tree: mlp.DecisionTree | None = None,
        out_dir: str | Path | None = None,
        sharding: jax.sharding.Sharding | None = None,
        exec_config: ExecutorConfig | None = None,
        spec_hash: str | None = None,
        injector=None,
        stats_recorder=None,
    ):
        self.config = config
        self.data = data_source
        self.tree = tree
        self.out_dir = Path(out_dir) if out_dir else None
        self.sharding = sharding
        self.exec_config = exec_config or ExecutorConfig()
        self.spec_hash = spec_hash  # provenance stamp (api/spec.py hash)
        self.injector = injector  # faults.FaultInjector (persist-path hook)
        # streaming.stats.StatsRecorder (or any callable taking
        # (window, values, moments)): observes each full window's staged
        # values + moments before the fit donates the buffer, so merge-able
        # sufficient statistics can be persisted without a second read.
        self.stats_recorder = stats_recorder
        self.cache = ReuseCache()
        self.spans = SpanRecorder()
        if ("ml" in config.method or config.method == "sampling") and tree is None:
            raise ValueError(f"method {config.method!r} requires a decision tree")
        if config.select_backend == "device":
            platform = (next(iter(sharding.device_set)).platform
                        if sharding is not None else jax.default_backend())
            if platform in DEVICE_SELECT_REFUSED_PLATFORMS:
                raise ValueError(
                    f"select_backend='device' is refused on {platform}: its "
                    "grouping keys rint(x / group_tol) need IEEE float64, and "
                    f"{platform}'s emulated float64 rounds some quotients one "
                    "unit away from the host's, which can split or merge "
                    "groups. Use select_backend='host' (the default).")

        self._moments, self._fit_all, self._fit_pred, self._gather = _jitted_fns(
            tuple(config.types), config.num_bins, config.mode, config.fit_backend
        )
        self._sel_fns = (
            _jitted_select_fns(
                tuple(config.types), config.num_bins, config.mode,
                config.fit_backend, config.group_tol,
            )
            if config.select_backend == "device"
            else None
        )
        self._key_buf: np.ndarray | None = None  # cached (P, 2) quantize buffer
        self._tree_arrays = tree.as_device() if tree else None
        # One StepMonitor per stage: medians/straggler flags per stage. The
        # load monitor's grace floor is configurable so chaos tests can
        # exercise speculation without second-long stalls; under
        # speculation the load monitor sees one start/finish per *attempt*
        # (deque/dict ops are GIL-atomic, failed attempts are abandoned so
        # they never enter the straggler median).
        self.monitors = {
            "load": StepMonitor(StragglerPolicy(
                grace_seconds=self.exec_config.straggler_grace_s)),
            "compute": StepMonitor(),
            "persist": StepMonitor(),
        }
        self.last_report: ExecutorReport | None = None
        # Per-run fault bookkeeping: {slice -> counter dict} + quarantined
        # unit records, reset by run(); the lock covers prefetch-thread vs
        # compute-thread increments.
        self._fault_lock = threading.Lock()
        self._fault_counts: dict[int, dict[str, int]] = {}
        self._spec_pool: futures.ThreadPoolExecutor | None = None
        # Host buffers for sources whose load_window reads into ``out``
        # (FileCubeSource): flat float32, each back on this list only once
        # the transfer staged from it has landed (_load_unit).
        self._fills_out = _takes_out(data_source)
        # Pieces are staged only to one device (a wider sharding would
        # split each piece across devices, not the window).
        self._one_device = sharding is None or len(sharding.device_set) == 1
        self._free_buffers: list[np.ndarray] = []
        self._free_lock = threading.Lock()

    # -- load stage -----------------------------------------------------------

    def _stage(self, values: np.ndarray) -> jax.Array:
        # Host -> the shard's own device in one copy: staging through
        # jnp.asarray first would land every window on the default device.
        if self.sharding is not None:
            staged = jax.device_put(np.asarray(values, np.float32), self.sharding)
        else:
            staged = jnp.asarray(values, dtype=jnp.float32)
        self.spans.count("bytes_h2d", staged.nbytes)
        return staged

    def _new_buffer(self, size: int) -> np.ndarray:
        return np.empty(size, np.float32)

    def _read(self, w: regions.Window, whole: bool = False
              ) -> tuple[np.ndarray | None, np.ndarray, list[jax.Array]]:
        """``(buffer, window, pieces)``. Where the source reads into ``out``,
        the window lands in a host buffer taken from the free-list (counted
        ``read_recycled``) or made anew; else ``buffer`` is None and the
        window a fresh array. A window of two ``_MIN_PIECE_BYTES`` or more,
        bound for one device and not ``whole``, is read in pieces of whole
        lines, each piece's transfer enqueued before the next piece is read:
        ``pieces`` are those staged arrays, else empty (the caller stages
        the window whole)."""
        ppl = self.data.geometry.points_per_line
        buf, recycled = None, False
        if self._fills_out:
            shape = (w.num_lines * ppl, self.data.slice_observations(w.slice_i))
            size = shape[0] * shape[1]
            with self._free_lock:
                fits = [i for i, b in enumerate(self._free_buffers) if b.size >= size]
                buf = self._free_buffers.pop(fits[-1]) if fits else None
            recycled = buf is not None
            if buf is None:
                buf = self._new_buffer(size)
            raw = buf[:size].reshape(shape)
        else:
            raw = self.data.load_window(w)  # (P, n_obs)
        bounds = ([0, w.num_lines] if whole or not self._one_device
                  else _piece_lines(w.num_lines, raw.nbytes))
        pieces = []
        for lo, hi in zip(bounds, bounds[1:]):
            piece = raw[lo * ppl:hi * ppl]
            if buf is not None:
                self.data.load_window(regions.Window(
                    w.slice_i, w.line_start + lo, w.line_start + hi), out=piece)
            if len(bounds) > 2:
                pieces.append(self._stage(piece))
        if recycled:
            self.spans.count("read_recycled")
        return buf, raw, pieces

    def _give_back(self, buf: np.ndarray, staged: jax.Array) -> None:
        """Return ``buf`` to the free-list once ``staged``'s transfer has
        landed — unless the runtime made the device array a view of it (the
        CPU backend may, for an aligned buffer): then the array owns it."""
        lo = buf.ctypes.data
        hi = lo + buf.nbytes
        if any(lo <= s.data.unsafe_buffer_pointer() < hi
               for s in staged.addressable_shards):
            return
        with self._free_lock:
            if len(self._free_buffers) < _FREE_BUFFERS:
                self._free_buffers.append(buf)

    def _load_unit(self, unit: regions.WorkUnit, uid: str | None = None,
                   whole: bool = False) -> _StagedWindow:
        """Load + H2D-stage one window (host work only — device kernels stay
        on the compute stage); runs on the prefetch thread when prefetch is
        enabled, or on speculation-pool threads under re-dispatch. ``uid``
        distinguishes attempts of the same unit in the load monitor; failed
        attempts are abandoned (no duration recorded) so an injected stall
        cannot poison the straggler median. A large window is read in line
        pieces whose transfers run under the next piece's read (``_read``;
        ``whole`` stages it in one); the pieces are assembled on the device
        inside ``pdf.load.h2d``, which then holds only the transfer's
        unhidden tail. A window read into a recycled buffer, or in pieces,
        waits there for its staged array to land before the buffer goes
        back; two loads of one window (speculation) take two buffers."""
        mon = self.monitors["load"]
        uid = uid or unit.unit_id
        ids = _ids(unit.window)
        try:
            with self.spans.span("pdf.load.read", **ids) as read:
                mon.start(uid, now=read.start)
                buf, raw, pieces = self._read(unit.window, whole)
            self.spans.count("bytes_read", raw.nbytes)
            self.spans.count("load_pieces", max(1, len(pieces)))
            with self.spans.span("pdf.load.h2d", start=read.end, **ids) as h2d:
                values = _assemble(*pieces) if pieces else self._stage(raw)
                if buf is not None or pieces:
                    values.block_until_ready()
                if buf is not None:
                    self._give_back(buf, values)
        except BaseException:
            mon.abandon(uid)
            raise
        mon.finish(uid, now=h2d.end)
        return _StagedWindow(unit, values, h2d.end - read.start)

    # -- fault tolerance: retry, speculation, quarantine (DESIGN.md §14) -------

    def _note_fault(self, slice_i: int, key: str, n: int = 1):
        with self._fault_lock:
            c = self._fault_counts.setdefault(
                slice_i,
                {"retries": 0, "speculations": 0, "speculation_wins": 0},
            )
            c[key] += n

    def _backoff(self, unit: regions.WorkUnit, attempt: int) -> float:
        """Exponential backoff with *deterministic* jitter: hashed from
        (unit, attempt) so a re-run backs off identically — randomness
        would be the one nondeterminism in an otherwise replayable failure
        path. Jitter in [0.5x, 1.5x) still de-correlates units that failed
        together (the thundering-herd concern jitter exists for)."""
        h = hashlib.sha256(f"{unit.unit_id}:{attempt}".encode()).digest()
        jitter = 0.5 + h[0] / 256.0
        return self.exec_config.retry_backoff_s * (2 ** attempt) * jitter

    def _pool(self) -> futures.ThreadPoolExecutor:
        # 4 workers: a straggling loser may still occupy one while the next
        # unit's primary + speculative pair runs — 2 would deadlock behind it.
        if self._spec_pool is None:
            self._spec_pool = futures.ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="load-spec")
        return self._spec_pool

    def _load_speculative(self, unit: regions.WorkUnit,
                          uid: str) -> _StagedWindow:
        """One load attempt with straggler speculation: if the primary load
        exceeds max(threshold x trailing-median, grace), dispatch a
        bitwise-identical second load and take whichever finishes first
        (the Spark speculative-execution contract — safe because loads are
        deterministic and fits row-pure, so winner identity cannot change
        the result's bytes). Below ``min_samples`` completed loads there is
        no median and the attempt runs inline."""
        mon = self.monitors["load"]
        med = mon.median()
        if med is None:
            return self._load_unit(unit, uid=uid)
        pol = mon.policy
        limit = max(pol.threshold * med, pol.grace_seconds)
        pool = self._pool()
        primary = pool.submit(self._load_unit, unit, uid)
        done, _ = futures.wait([primary], timeout=limit)
        if primary in done:
            return primary.result()  # raises the load's own error if it failed

        # Straggler: re-dispatch. First *success* wins; the loser runs to
        # completion in the pool (its duration is a real completed load, so
        # letting it report is correct) and its staged buffer is dropped.
        self._note_fault(unit.window.slice_i, "speculations")
        if uid not in mon.flagged:
            mon.flagged.append(uid)
        spec = pool.submit(self._load_unit, unit, f"{uid}#spec")
        pending = {primary, spec}
        while pending:
            done, pending = futures.wait(
                pending, return_when=futures.FIRST_COMPLETED)
            for f in done:
                if f.exception() is None:
                    if f is spec:
                        self._note_fault(
                            unit.window.slice_i, "speculation_wins")
                    return f.result()
        raise primary.exception()  # both attempts failed

    def _load_guarded(self, unit: regions.WorkUnit):
        """The load stage's retry wrapper (the prefetcher's stage_fn):
        transient failures back off and re-attempt up to ``max_retries``
        times; exhaustion returns a ``_FailedUnit`` sentinel — raising here
        would kill the whole prefetch stream, and would reach the consumer
        wrapped in an opaque ``PrefetchError``. The run loop turns the
        sentinel into quarantine (degraded mode) or a clean per-unit error.
        Fatal errors — including ``ShardLostError`` — always raise."""
        ec = self.exec_config
        last: BaseException | None = None
        for attempt in range(ec.max_retries + 1):
            uid = unit.unit_id if attempt == 0 else f"{unit.unit_id}#r{attempt}"
            try:
                if ec.speculate:
                    return self._load_speculative(unit, uid)
                return self._load_unit(unit, uid=uid)
            except Exception as e:  # noqa: BLE001 — classified below
                if not is_transient(e):
                    raise
                last = e
                if attempt < ec.max_retries:
                    self._note_fault(unit.window.slice_i, "retries")
                    time.sleep(self._backoff(unit, attempt))
        return _FailedUnit(unit, _errstr(last), ec.max_retries + 1)

    # -- compute stage: ComputePDF&Error dispatch per method -------------------

    def _fit_launch(self, values: jax.Array, moments: dists.Moments):
        """Dispatch the fit of every row of ``values`` (the tree's predict
        folded in for the ml methods); returns device (type, params, err)."""
        if self._tree_arrays is not None and "ml" in self.config.method:
            return self._fit_pred(values, moments, self._tree_arrays)
        return self._fit_all(values, moments)

    def _fit(self, values: jax.Array, moments: dists.Moments, ids: dict,
             start: float | None = None):
        """Fit every row of ``values``; returns np arrays (type, params, err)."""
        with self.spans.span("pdf.fit.launch", start=start, **ids) as launch:
            fitted = self._fit_launch(values, moments)
        with self.spans.span("pdf.fit.wait", start=launch.end, **ids):
            return tuple(np.asarray(x) for x in fitted)

    def _quantized_keys(self, moments: dists.Moments) -> np.ndarray:
        """Host-side (mu, sigma) quantization into a cached (P, 2) buffer
        (one allocation per window size instead of five temporaries per
        window; sigma is derived from var on host to skip a device op).

        The actual arithmetic lives in ``grouping.quantize_keys_host`` — the
        single definition of the key semantics, which the device path
        (``grouping.quantize_keys_from_var``) matches bit-for-bit. The
        previous inline version fed the f32 mean straight to ``np.divide``
        with an f64 ``out``, which numpy computes on the *f32* loop — at
        mean ~ 3e3 and tol = 1e-6 the ~3e9 quotient aliased on f32's 2^24
        grid in ~256-step buckets, merging points whose means differ by
        ~256x the configured tolerance (the exact failure this path's
        docstring claimed to have fixed)."""
        mean = np.asarray(moments.mean)
        var = np.asarray(moments.var)
        p = mean.shape[0]
        if self._key_buf is None or self._key_buf.shape[0] != p:
            self._key_buf = np.empty((p, 2), dtype=np.int64)
            self._key_tmp = np.empty((p,), dtype=np.float64)
        return grp.quantize_keys_host(
            mean, var, self.config.group_tol, out=self._key_buf, tmp=self._key_tmp
        )

    def _select_and_fit(self, values: jax.Array, moments: dists.Moments,
                        window: regions.Window,
                        sample_idx: np.ndarray | None = None,
                        total_points: int | None = None,
                        start: float | None = None):
        """The Select step (§5.1/5.2): returns per-point results + bookkeeping.

        Dispatches on ``config.select_backend``: 'host' dedups via np.unique
        over host-quantized keys, 'device' keeps the dedup on the
        accelerator. Both are bitwise-equivalent (the device keys are exact
        hi/lo splits of the host int64 keys, and fits are row-deterministic).
        ``window``/``sample_idx``/``total_points`` only feed the sampling
        method (for every other method ``values`` covers the whole window).
        ``start``: a clock read taken just before (the end of the moments
        span), at which the first span here begins; the spans then tile the
        step (``pdf.select``, ``pdf.fit.launch``, ``pdf.fit.wait``).
        """
        method = self.config.method
        num_points = values.shape[0]
        ids = _ids(window)
        if method == "sampling":
            with self.spans.span("pdf.select", start=start, **ids):
                return self._sample_classify(
                    moments, window, total_points or num_points, sample_idx
                )
        if method in ("baseline", "ml"):
            t, p, e = self._fit(values, moments, ids, start)
            self.spans.count("fit_rows", num_points)
            self.spans.count("fit_rows_padded", num_points)
            return t, p, e, num_points, 0
        if self._sel_fns is not None:
            return self._select_device(values, moments, ids, start)

        # grouping / reuse variants: dedup on host, fit representatives only.
        with self.spans.span("pdf.select", start=start, **ids) as sel:
            keys = self._quantized_keys(moments)
            groups = grp.group_host(keys)
            rep_keys = keys[groups.rep_indices]
        self.spans.count("groups", groups.num_groups)
        return self._fit_representatives(
            values, moments, rep_keys, groups.rep_indices, groups.inverse,
            ids, sel.end,
        )

    def _fit_representatives(
        self,
        values: jax.Array,
        moments: dists.Moments,
        rep_keys: np.ndarray,
        rep_rows: np.ndarray,
        inverse,
        ids: dict,
        start: float,
    ):
        """Fit one row per group — the Select core shared by both backends.

        ``rep_keys`` (G, 2) int64 is each group's cache identity; ``rep_rows``
        (G,) the representatives' window row indices; ``inverse`` (P,) each
        point's group. Consults the reuse cache when the method carries one,
        fits the misses via the padded re-dispatch, and returns per-*point*
        results ``(t, p, e, fitted, cache_hits)``. Its ``pdf.fit.launch``
        span (the cache lookup, the gather and the fit's dispatch) starts at
        ``start``, the end of the Select span before it, and ``pdf.fit.wait``
        (the copy to the host and the scatter per point) follows on."""
        method = self.config.method
        g = len(rep_rows)
        launched = sub_vals = sub_mom = None
        with self.spans.span("pdf.fit.launch", start=start, **ids) as launch:
            if method.startswith("reuse"):
                hit, cached = self.cache.lookup_window(rep_keys)
                cache_hits = int(hit.sum())
                todo = rep_rows[~hit]
            else:
                hit = np.zeros((g,), dtype=bool)
                cached = np.zeros((g, 5))
                todo = rep_rows
                cache_hits = 0

            rep_t = np.zeros((g,), dtype=np.int32)
            rep_p = np.zeros((g, 3), dtype=np.float32)
            rep_e = np.zeros((g,), dtype=np.float32)
            rep_t[hit] = cached[hit, 0].astype(np.int32)
            rep_p[hit] = cached[hit, 1:4]
            rep_e[hit] = cached[hit, 4]

            if len(todo):
                padded = grp.pad_representatives(todo, self.config.rep_bucket)
                # Single device gather for values + all moment fields (the
                # old per-field np.asarray round-trips cost ~7 transfers per
                # window).
                sub_vals, sub_mom = self._gather(values, moments,
                                                 jnp.asarray(padded))
                launched = self._fit_launch(sub_vals, sub_mom)  # ML per method
                self.spans.count("fit_rows", len(todo))
                self.spans.count("fit_rows_padded", len(padded))

        with self.spans.span("pdf.fit.wait", start=launch.end, **ids):
            if launched is not None:
                t, p, e = (np.asarray(x)[: len(todo)] for x in launched)
                rep_t[~hit], rep_p[~hit], rep_e[~hit] = t, p, e
                if method.startswith("reuse"):
                    self.cache.insert_window(
                        rep_keys[~hit],
                        np.concatenate(
                            [t[:, None], p, e[:, None]], axis=-1
                        ).astype(np.float64),
                    )
            inv = np.asarray(inverse)
            out = rep_t[inv], rep_p[inv], rep_e[inv], len(todo), cache_hits
            # the fit's device buffers are released here, inside the span
            del launched, sub_vals, sub_mom
        return out

    def _select_device(self, values: jax.Array, moments: dists.Moments,
                       ids: dict, start: float | None = None):
        """Device-side Select (select_backend='device'): the grouping hot
        path never leaves the accelerator. ``probe`` quantizes + sorts on
        device; the only D2H is the scalar group count (needed to pick the
        static padded batch), after which one launch gathers the
        representatives, fits them, and scatters per-point results — no
        (P, 2) key download, no np.unique, no rep-index upload.

        The reuse methods keep the host cache (its store is a host dict by
        design) but swap the np.unique dedup for the device partition: only
        the compacted (G,) representative keys and the (P,) slot map come
        down, and cache misses reuse the existing padded re-dispatch, so
        results — and the evolving cache contents — stay bitwise-identical
        to the host path."""
        method = self.config.method
        fns = self._sel_fns
        grouping = method.startswith("grouping")
        with self.spans.span("pdf.select", start=start, **ids) as sel:
            num_g, rep_for_point, is_rep = fns.probe(moments)
            g = int(num_g)  # the one sync of the device Select path
            padded_g = grp.padded_size(g, self.config.rep_bucket)
            if not grouping:
                # reuse / reuse_ml: device dedup + host cache — only the
                # compacted (G,) rep keys/rows and the (P,) slot map come
                # down, then the representative-fit core runs exactly as on
                # the host path.
                gather_idx, rep_keys4, point_slot = fns.compact(
                    moments, rep_for_point, is_rep, padded_g
                )
                rep_rows = np.asarray(gather_idx)[:g].astype(np.int64)
                rep_keys = grp.keys_to_int64(np.asarray(rep_keys4)[:g])  # (G, 2)
        self.spans.count("groups", g)
        if not grouping:
            return self._fit_representatives(
                values, moments, rep_keys, rep_rows, point_slot, ids, sel.end
            )

        with self.spans.span("pdf.fit.launch", start=sel.end, **ids) as launch:
            if self._tree_arrays is not None and "ml" in method:
                launched = fns.select_fit_pred(
                    values, moments, rep_for_point, is_rep,
                    self._tree_arrays, padded_g,
                )
            else:
                launched = fns.select_fit_all(
                    values, moments, rep_for_point, is_rep, padded_g
                )
        self.spans.count("fit_rows", g)
        self.spans.count("fit_rows_padded", padded_g)
        with self.spans.span("pdf.fit.wait", start=launch.end, **ids):
            t, p, e = (np.asarray(x) for x in launched)
        return t, p, e, g, 0

    def _sample_seed(self, w: regions.Window) -> int:
        """Per-window draw seed from (sample_seed, slice, line): results do
        not depend on window execution order and survive resume."""
        return (self.config.sample_seed * 1_000_003 + w.slice_i * 100_003
                + w.line_start)

    def _draw_sample(self, num_points: int, w: regions.Window) -> np.ndarray:
        """The random sampler's index draw — needs only the window's point
        count, so the compute stage can subset the window *before* the
        moments pass (§5.4's cost is meant to fall with the rate)."""
        from repro.core import sampling as smp

        return smp.sample_indices_random(
            num_points, self.config.sample_frac, seed=self._sample_seed(w)
        )

    def _sample_classify(self, moments: dists.Moments, w: regions.Window,
                         num_points: int, idx: np.ndarray | None):
        """method='sampling' (§5.4, Algorithm 5): classify the sampled
        points' types with the decision tree (grouping-first dedup, Alg. 5
        lines 15-26) — no Eq.-5 fitting at all, which is the method's
        entire speedup. Unsampled points get ``type_idx = -1`` and zero
        params/error; ``SliceResult.features`` aggregates over the sampled
        subset only.

        ``idx`` is the pre-drawn random sample (``moments`` then cover only
        those rows — the run loop subsets the window before the moments
        pass, so load-side device work scales with the rate). For the
        k-means sampler ``idx`` is None: double sampling clusters on every
        point's (mu, sigma), so it inherently needs the full moments pass
        (the paper's extra cost for k-means, Fig. 16)."""
        from repro.core import sampling as smp

        cfg = self.config
        mean = np.asarray(moments.mean)
        var = np.asarray(moments.var)
        std = np.sqrt(np.maximum(var, 0.0))
        if idx is None:  # kmeans: cluster over the full window's features
            idx = smp.sample_indices_kmeans(
                np.stack([mean, std], axis=-1), cfg.sample_frac,
                iters=cfg.kmeans_iters, seed=self._sample_seed(w),
            )
            sub_mean, sub_std = mean[idx], std[idx]
            sub_skew = np.asarray(moments.skew)[idx]
            sub_kurt = np.asarray(moments.kurt)[idx]
        else:  # random: moments were computed on the sampled rows only
            sub_mean, sub_std = mean, std
            sub_skew = np.asarray(moments.skew)
            sub_kurt = np.asarray(moments.kurt)

        pred = smp.predict_types(
            sub_mean, sub_std, self.tree, group_tol=cfg.group_tol,
            skew=sub_skew, kurt=sub_kurt,
        )
        t = np.full((num_points,), -1, dtype=np.int32)
        t[idx] = pred
        params = np.zeros((num_points, 3), dtype=np.float32)
        err = np.zeros((num_points,), dtype=np.float32)
        # 'fitted' reports the classified sample count (nothing runs through
        # ComputePDF&Error for this method — that is the point).
        return t, params, err, len(idx), 0

    # -- run (Algorithm 1 over a Plan) -----------------------------------------

    def run(
        self,
        plan: regions.Plan,
        resume: bool = False,
        on_window: Callable[[WindowStats], None] | None = None,
    ) -> dict[int, SliceResult]:
        """Execute every unit of ``plan``; returns one SliceResult per slice.

        Pass the *full* plan even when resuming — completed windows are
        filtered against each slice's watermark here and their results
        restored from the persisted ``.npz`` files.
        """
        ppl = self.data.geometry.points_per_line
        requested = plan.slices
        snapshot = self.spans.snapshot()
        edge_ids = {"slice": requested[0] if len(requested) == 1 else -1,
                    "line": -1}
        with self.spans.span("pdf.slice.open", **edge_ids):
            persist, outs, units, stream, prefetcher, wall0 = self._open(
                plan, resume)
        stats: dict[int, list[WindowStats]] = {s: [] for s in requested}
        quarantined: dict[int, list[dict]] = {s: [] for s in requested}
        load_total = wait_total = compute_total = 0.0
        k = 0  # units taken off the stream: the next one is units[k]
        try:
            while True:
                wait_ids = (_ids(units[k].window) if k < len(units)
                            else edge_ids)
                wait_name = "pdf.load.first" if k == 0 and units else "pdf.load.wait"
                with self.spans.span(wait_name, **wait_ids) as wait:
                    try:
                        item = next(stream, None)
                    except PrefetchError as pe:
                        # Shard death must surface as itself: the
                        # scheduler's re-deal catches ShardLostError, not
                        # the prefetch wrapper it crossed the thread
                        # boundary in.
                        if isinstance(pe.__cause__, ShardLostError):
                            raise pe.__cause__
                        raise
                if item is None:
                    break
                k += 1
                # wait_s: the only load-stage time the device was blocked on
                # (serial mode does the whole load inline here, so wait ==
                # load by construction; with prefetch it is the shortfall).
                wait_s = wait.seconds

                if not isinstance(item, _FailedUnit):
                    item = self._compute_with_retry(item)
                if isinstance(item, _FailedUnit):
                    if not self.exec_config.degraded_mode:
                        raise RuntimeError(
                            f"work unit {item.unit.unit_id} failed after "
                            f"{item.attempts} attempts: {item.error}")
                    self._quarantine(item, outs, ppl, quarantined)
                    continue

                with self.spans.span("pdf.handoff", **_ids(item.window)):
                    (w, t, p, e, moments, sample_idx, fitted, hits,
                     comp_s, _load_s) = item
                    mom_np = _moments_np(moments)
                    o = outs[w.slice_i]
                    lo, hi = w.line_start * ppl, w.line_end * ppl
                    o["type_idx"][lo:hi], o["params"][lo:hi], o["error"][lo:hi] = t, p, e
                    if sample_idx is None:
                        for name, col in zip(("mean", "std", "skew", "kurt"), mom_np):
                            o[name][lo:hi] = col
                    else:
                        # random sampling computed moments for the sampled
                        # rows only; unsampled rows stay zero (their
                        # type_idx is -1)
                        for name, col in zip(("mean", "std", "skew", "kurt"), mom_np):
                            o[name][lo:hi][sample_idx] = col

                    ws = WindowStats(w, hi - lo, fitted, item.load_seconds,
                                     comp_s, hits, wait_s)
                    stats[w.slice_i].append(ws)
                    load_total += item.load_seconds
                    wait_total += wait_s
                    compute_total += comp_s
                    self.spans.count("windows")
                    self.spans.count("points", hi - lo)

                    persist.submit(
                        w.slice_i, w, {name: o[name][lo:hi] for name in _FIELDS}
                    )
                    if on_window:
                        on_window(ws)
        except BaseException:
            with self.spans.span("pdf.slice.drain", **edge_ids):
                self._close(prefetcher, persist)
            raise

        with self.spans.span("pdf.slice.drain", **edge_ids):
            self._close(prefetcher, persist)
            persist.raise_if_failed()
            if self.out_dir is not None:
                for s in requested:
                    persist.write_failed_manifest(s, quarantined[s])
            wall = time.perf_counter() - wall0
            counts = self._fault_counts
            results: dict[int, SliceResult] = {}
            for s in requested:
                o = outs[s]
                avg_err = float(o["error"].mean())
                c = counts.get(s, {})
                r = SliceResult(o["type_idx"], o["params"], o["error"], o["mean"],
                                o["std"], o["skew"], o["kurt"], avg_err, stats[s],
                                slice_i=s, spec_hash=self.spec_hash,
                                retries=c.get("retries", 0),
                                speculations=c.get("speculations", 0),
                                quarantined=tuple(quarantined[s]))
                if self.config.error_bound is not None:
                    r.error_bound_satisfied = avg_err <= self.config.error_bound
                results[s] = r
        spans, counters = self.spans.since(snapshot)
        self.last_report = ExecutorReport(
            wall_seconds=wall,
            units=sum(len(v) for v in stats.values()),
            load_seconds=load_total,
            wait_seconds=wait_total,
            compute_seconds=compute_total,
            persist_seconds=persist.seconds,
            retries=sum(c["retries"] for c in counts.values()),
            speculations=sum(c["speculations"] for c in counts.values()),
            speculation_wins=sum(
                c["speculation_wins"] for c in counts.values()),
            quarantined=sum(len(v) for v in quarantined.values()),
            spans=spans,
            counters=counters,
        )
        return results

    def _open(self, plan: regions.Plan, resume: bool):
        """A run's set-up (the ``pdf.slice.open`` span): the persist stage,
        the output buffers, the units left after resume, and the load
        stream (the prefetcher started)."""
        geom = self.data.geometry
        ppl = geom.points_per_line
        total = geom.points_per_slice
        requested = plan.slices
        persist = PersistStage(
            self.out_dir,
            async_writes=self.exec_config.async_persist,
            monitor=self.monitors["persist"],
            spec_hash=self.spec_hash,
            injector=self.injector,
            total_lines=geom.lines_per_slice,
            spans=self.spans,
        )

        outs = {
            s: {
                "type_idx": np.zeros((total,), dtype=np.int32),
                "params": np.zeros((total, 3), dtype=np.float32),
                "error": np.zeros((total,), dtype=np.float32),
                "mean": np.zeros((total,), dtype=np.float32),
                "std": np.zeros((total,), dtype=np.float32),
                "skew": np.zeros((total,), dtype=np.float32),
                "kurt": np.zeros((total,), dtype=np.float32),
            }
            for s in requested
        }

        units = list(plan.units)
        if resume and self.out_dir is not None:
            infos = {s: persist.watermark_info(s) for s in requested}
            for s, info in infos.items():
                persist.check_resume_hash(s, info)
            marks = {s: int(info["next_line"]) for s, info in infos.items()}
            # Units a previous degraded run quarantined sit *below* the
            # watermark with no persisted .npz — the failed-unit manifest
            # is what re-includes them, so a fault-free resume repairs the
            # hole (and clears the manifest below).
            failed_prev = {s: persist.failed_lines(s) for s in requested}
            for s, mark in marks.items():
                if mark > 0:
                    persist.restore_windows(s, mark, ppl, outs[s])
            units = [
                u for u in units
                if u.window.line_start >= marks[u.window.slice_i]
                or u.window.line_start in failed_prev[u.window.slice_i]
            ]

        # retry/speculation threads bump these via _note_fault under the
        # same lock; an unlocked reset here raced a concurrent bump (the
        # LOCK rule's first true positive)
        with self._fault_lock:
            self._fault_counts = {}
        wall0 = time.perf_counter()
        prefetcher = None
        if self.exec_config.prefetch and units:
            prefetcher = WindowPrefetcher(
                units, self._load_guarded, depth=self.exec_config.prefetch_depth
            )
            stream = iter(prefetcher)
        else:
            stream = (self._load_guarded(u) for u in units)
        return persist, outs, units, stream, prefetcher, wall0

    def _close(self, prefetcher, persist: "PersistStage"):
        """Stop the load stage and flush the persist stage: the watermark
        is durable before the run returns or re-raises."""
        if prefetcher is not None:
            prefetcher.close()
        persist.close()
        if self._spec_pool is not None:
            self._spec_pool.shutdown(wait=False, cancel_futures=True)
            self._spec_pool = None

    class _ComputedWindow(NamedTuple):
        """One computed window: everything the run loop scatters/persists."""

        window: regions.Window
        type_idx: np.ndarray
        params: np.ndarray
        error: np.ndarray
        moments: tuple  # device (mean, var, skew, kurt, ...)
        sample_idx: np.ndarray | None
        fitted: int
        cache_hits: int
        compute_seconds: float
        load_seconds: float = 0.0

    def _compute_window(self, item: _StagedWindow,
                        attempt: int = 0) -> "_ComputedWindow":
        """The compute-stage body for one staged window (moments + Select &
        fit) — factored out of the run loop so it can be retried as a unit."""
        cmon = self.monitors["compute"]
        unit = item.unit
        w = unit.window
        uid = unit.unit_id if attempt == 0 else f"{unit.unit_id}#c{attempt}"
        values = item.values
        total_points = values.shape[0]
        sample_idx = None
        if (self.config.method == "sampling"
                and self.config.sampler == "random"):
            # §5.4's entire point: only the sampled fraction is touched —
            # subset the window on device *before* the moments pass, so
            # per-window device work (and the figure-15 cost curve) scales
            # with the rate. k-means keeps the full pass: it clusters on
            # every point's (mu, sigma) by construction.
            sample_idx = self._draw_sample(total_points, w)
        ids = _ids(w)
        with self.spans.span("pdf.moments", **ids) as sp:
            if sample_idx is not None:
                values = values[jnp.asarray(sample_idx)]
            moments = jax.block_until_ready(self._moments(values))
        if self.stats_recorder is not None and sample_idx is None:
            # Must run before _select_and_fit: the fit executables donate
            # ``values``. Sampled windows are skipped — their stats describe
            # a draw, not the window, and cannot merge with append data.
            with self.spans.span("pdf.stats", **ids) as sp:
                self.stats_recorder(w, values, dists.Moments(*moments))
        t1 = sp.end  # the compute stage starts where the last span ended
        cmon.start(uid, now=t1)
        try:
            t, p, e, fitted, hits = self._select_and_fit(
                values, dists.Moments(*moments), w,
                sample_idx=sample_idx, total_points=total_points, start=t1,
            )
        except BaseException:
            cmon.abandon(uid)
            raise
        t2 = time.perf_counter()
        cmon.finish(uid, now=t2)
        return self._ComputedWindow(w, t, p, e, moments, sample_idx, fitted,
                                    hits, t2 - t1, item.load_seconds)

    def _compute_with_retry(self, item: _StagedWindow):
        """Compute one staged window, retrying transient failures with a
        *fresh load* each time — the fit executables donate the staged
        buffer, so after any fit dispatch the old device array must be
        treated as consumed. Returns a ``_ComputedWindow``, or a
        ``_FailedUnit`` after exhaustion (the run loop quarantines it in
        degraded mode, or raises a per-unit error outside it)."""
        ec = self.exec_config
        unit = item.unit
        last: BaseException | None = None
        for attempt in range(ec.max_retries + 1):
            try:
                if item is None:
                    item = self._load_unit(unit, uid=f"{unit.unit_id}#c{attempt}")
                return self._compute_window(item, attempt)
            except Exception as e:  # noqa: BLE001 — classified below
                if not is_transient(e):
                    raise
                last = e
                item = None  # possibly-donated buffer: reload next attempt
                if attempt < ec.max_retries:
                    self._note_fault(unit.window.slice_i, "retries")
                    time.sleep(self._backoff(unit, attempt))
        return _FailedUnit(unit, _errstr(last), ec.max_retries + 1)

    def _quarantine(self, failed: _FailedUnit, outs: dict, ppl: int,
                    quarantined: dict[int, list[dict]]):
        """Degraded mode's terminal state for a unit: its points carry
        ``type_idx = -1`` (the established unclassified marker) and zero
        params/moments, nothing is persisted for the window (the manifest —
        not a fabricated .npz — records the hole), and the run continues."""
        w = failed.unit.window
        o = outs[w.slice_i]
        lo, hi = w.line_start * ppl, w.line_end * ppl
        o["type_idx"][lo:hi] = -1
        for name in ("params", "error", "mean", "std", "skew", "kurt"):
            o[name][lo:hi] = 0
        quarantined[w.slice_i].append({
            "unit_id": failed.unit.unit_id,
            "line_start": int(w.line_start),
            "line_end": int(w.line_end),
            "attempts": int(failed.attempts),
            "error": failed.error,
        })

    def run_slice(
        self,
        slice_i: int,
        resume: bool = False,
        on_window: Callable[[WindowStats], None] | None = None,
    ) -> SliceResult:
        plan = regions.build_plan(
            self.data.geometry, [slice_i], self.config.window_lines
        )
        return self.run(plan, resume=resume, on_window=on_window)[slice_i]

    # -- externally-batched work units (the serving layer's entry points) ------

    def run_window_batch(
        self, windows: list[regions.Window]
    ) -> list[WindowResult]:
        """Compute many windows with shared device launches — the warm
        executor's entry point for externally-batched work (the serving
        layer's coalesced tick; ``windows`` must be distinct, in any order,
        possibly spanning slices).

        Per-point results are **bitwise-identical** to running each window
        through ``run_window``, by construction: every launch the batch
        issues has the exact shape the serial path would compile for, so
        both paths execute the same XLA executables — and within one
        executable per-row results are position- and content-independent
        (moments and fits are row-pure; padding rows and neighbours cannot
        perturb a row's bits). Concretely:

        * moments run per window at the window's own shape — only their
          *dispatch* is shared (all launched asynchronously, one barrier),
          which removes the serial path's per-window sync.
        * the grouped methods' representative fits are packed: each
          window's Select (quantize → group → representative choice) is
          made per window exactly as serially, then whole windows whose
          serial fit shape class (``grp.padded_size(groups, rep_bucket)``)
          matches are packed into one gather + fit launch of that shape —
          many windows' representatives per dispatch, same executable as
          each window's solo fit.

        Naively concatenating windows into one big launch is ~2x fewer
        dispatches still, but a different-shaped executable vectorizes
        reductions differently and drifts results by ~1 ulp — the serving
        layer's equivalence contract (DESIGN.md §13) forbids exactly that.

        Three methods fall back to per-window ``run_window`` dispatch, by
        design: ``sampling`` (its cost is host-side classification; there
        is no device fit to share), the ``reuse`` variants (cache-hit
        values depend on insertion order, so batching lookups would serve
        different — not just differently-counted — fits), and any method
        under ``select_backend='device'`` (its gather→fit→scatter is fused
        into one per-window executable there)."""
        if not windows:
            return []
        if len({(w.slice_i, w.line_start) for w in windows}) != len(windows):
            raise ValueError("run_window_batch windows must be distinct")
        method = self.config.method
        if (method == "sampling" or method.startswith("reuse")
                or self._sel_fns is not None):
            return [self.run_window(w) for w in windows]

        lmon = self.monitors["load"]
        raws = []
        for w in windows:
            uid = f"batch:s{w.slice_i}/l{w.line_start:05d}"
            lmon.start(uid, now=time.perf_counter())
            raws.append(self.data.load_window(w))
            lmon.finish(uid, now=time.perf_counter())
        if self.sharding is None and len(raws) > 1:
            # one H2D for the whole batch, sliced back into window-shaped
            # device arrays (same f32 bits; slicing is pure data movement)
            bounds = np.cumsum([0] + [r.shape[0] for r in raws])
            cat = self._stage(np.concatenate(raws, axis=0))
            staged = [cat[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
        else:
            staged = [self._stage(r) for r in raws]
        pending = [self._moments(v) for v in staged]  # async; barrier below
        moments = [dists.Moments(*jax.block_until_ready(m)) for m in pending]

        cmon = self.monitors["compute"]
        uid = (f"batch:s{windows[0].slice_i}/l{windows[0].line_start:05d}"
               f"x{len(windows)}")
        cmon.start(uid, now=time.perf_counter())
        if method in ("baseline", "ml"):
            # per-window fit launches (the serial shape), dispatched without
            # intermediate syncs; host conversion after the last dispatch
            if self._tree_arrays is not None and "ml" in method:
                fits = [self._fit_pred(v, m, self._tree_arrays)
                        for v, m in zip(staged, moments)]
            else:
                fits = [self._fit_all(v, m) for v, m in zip(staged, moments)]
            per = [tuple(np.asarray(x) for x in f) for f in fits]
        else:
            per = self._select_and_fit_packed(windows, staged, moments)
        cmon.finish(uid, now=time.perf_counter())

        out = []
        for w, m, (t, p, e) in zip(windows, moments, per):
            out.append(WindowResult(
                w, t, p, e,
                np.asarray(m.mean),
                np.sqrt(np.maximum(np.asarray(m.var), 0)),
                np.asarray(m.skew), np.asarray(m.kurt)))
        return out

    def _select_and_fit_packed(self, windows: list, staged: list,
                               moments: list):
        """Grouped Select over a window batch: quantize + dedup per window
        on host (grouping scope = the window, as Algorithm 3 defines it),
        then pack whole windows of the same serial fit-shape class into
        shared gather + fit launches of exactly that shape. Returns
        per-window per-point ``(t, p, e)`` in window order."""
        bucket = self.config.rep_bucket
        infos = [grp.group_host(self._quantized_keys(m)) for m in moments]

        # pack: greedy fill within each shape class, preserving window order
        classes: dict[int, list[int]] = {}
        for i, g in enumerate(infos):
            classes.setdefault(grp.padded_size(g.num_groups, bucket),
                               []).append(i)
        launches: list[tuple[int, list[int]]] = []
        for size, idxs in sorted(classes.items()):
            cur: list[int] = []
            cur_n = 0
            for i in idxs:
                n = infos[i].num_groups
                if cur and cur_n + n > size:
                    launches.append((size, cur))
                    cur, cur_n = [], 0
                cur.append(i)
                cur_n += n
            if cur:
                launches.append((size, cur))

        offsets = np.cumsum([0] + [v.shape[0] for v in staged])
        cat_vals = jnp.concatenate(staged, axis=0)
        cat_mom = dists.Moments(
            *(jnp.concatenate(f, axis=0) for f in zip(*moments)))

        results: list = [None] * len(staged)
        for size, idxs in launches:
            # padding slots repeat the first representative — discarded by
            # the inverse maps, and row-pure kernels make their content moot
            idx = np.full(
                (size,),
                int(infos[idxs[0]].rep_indices[0]) + int(offsets[idxs[0]]),
                dtype=np.int64)
            pos = 0
            for i in idxs:
                n = infos[i].num_groups
                idx[pos:pos + n] = infos[i].rep_indices + offsets[i]
                pos += n
            sub_vals, sub_mom = self._gather(cat_vals, cat_mom,
                                             jnp.asarray(idx))
            t, p, e = self._fit(sub_vals, sub_mom, _ids(windows[idxs[0]]))
            pos = 0
            for i in idxs:
                g = infos[i]
                n = g.num_groups
                inv = g.inverse
                results[i] = (t[pos:pos + n][inv], p[pos:pos + n][inv],
                              e[pos:pos + n][inv])
                pos += n
        return results

    def run_window(self, w: regions.Window) -> WindowResult:
        """ONE window through exactly the serial run-loop computation (load
        → moments → Select & fit), without persist: the per-window fallback
        of ``run_window_batch`` (method='sampling') and the serving layer's
        naive one-launch-per-query baseline."""
        item = self._load_unit(regions.WorkUnit(w, 0), whole=True)
        values = item.values
        total_points = values.shape[0]
        sample_idx = None
        if (self.config.method == "sampling"
                and self.config.sampler == "random"):
            sample_idx = self._draw_sample(total_points, w)
            values = values[jnp.asarray(sample_idx)]
        moments = jax.block_until_ready(self._moments(values))
        cmon = self.monitors["compute"]
        uid = f"one:s{w.slice_i}/l{w.line_start:05d}"
        cmon.start(uid, now=time.perf_counter())
        t, p, e, _fitted, _hits = self._select_and_fit(
            values, dists.Moments(*moments), w,
            sample_idx=sample_idx, total_points=total_points,
        )
        cmon.finish(uid, now=time.perf_counter())
        mom_np = _moments_np(moments)
        if sample_idx is None:
            mean, std, skew, kurt = mom_np
        else:
            # like the serial loop: unsampled rows stay zero (type_idx -1)
            mean, std, skew, kurt = (
                np.zeros((total_points,), dtype=np.float32) for _ in range(4))
            for dst, col in zip((mean, std, skew, kurt), mom_np):
                dst[sample_idx] = col
        return WindowResult(w, np.asarray(t), np.asarray(p), np.asarray(e),
                            mean, std, skew, kurt)

    # -- resume helpers (also used by the PDFComputer facade) ------------------

    def watermark(self, slice_i: int) -> int:
        return PersistStage(self.out_dir, async_writes=False).watermark(slice_i)
