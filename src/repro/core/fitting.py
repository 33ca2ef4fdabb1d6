"""Algorithm 3 (fit-all-types, keep min error) and Algorithm 4 (ML path).

The paper's Algorithm 3 loops over T candidate types, fitting and scoring
each; complexity O(T) in the number of types, with each iteration costing a
full pass over the n observation values (the external R program re-reads the
data). Algorithm 4 replaces the loop with a single fit of the decision-tree
predicted type.

Here both are dense, batched XLA computations over a window of points:

* ``mode='faithful'`` reproduces the paper's cost structure: the O(n)
  histogram pass is executed once per candidate type (T times for
  Algorithm 3, once for Algorithm 4). This is the paper-faithful baseline
  whose roofline/§Perf numbers are reported as "baseline".
* ``mode='fused'`` is the beyond-paper optimization: moments and the Eq.-5
  histogram depend only on the data, never on the candidate type, so they
  are computed once and shared across all T types. Both modes return
  bit-identical results (tests assert this).

Orthogonal to the mode, the *fit backend* selects how the device work is
implemented (``FIT_BACKENDS``):

* ``reference`` — pure-jnp chain (scatter-add histogram; the one-hot
  ``pe.histogram`` remains the test oracle only).
* ``kernels``   — the chain with the Pallas moments + histogram kernels
  swapped in (two kernel launches, masses still materialized in XLA).
* ``fused``     — the single-launch path (``kernels/fitpdf``): one kernel
  emits moments + Eq.-5 edges, a second streams the window once more,
  builds the histogram in VMEM and reduces the Eq.-5 error against the
  (small, XLA-evaluated) CDF masses in its epilogue, so only the (P, T)
  errors reach HBM. The default executor path.

``mode='faithful'`` deliberately keeps the per-type chain structure for
every backend — a fused single pass cannot represent the paper's per-type
data passes, so the fused backend falls back to the chain there.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import distributions as dists
from repro.core import pdf_error as pe

_BIG = 1e30

FIT_BACKENDS = ("reference", "kernels", "fused")


class FitResult(NamedTuple):
    """Per-point PDF: distribution type index, its 3-slot params, Eq.-5 error."""

    type_idx: jax.Array  # (...,) int32 into the candidate `types` tuple
    params: jax.Array  # (..., 3)
    error: jax.Array  # (...,)


def _finite_or_big(err: jax.Array) -> jax.Array:
    return jnp.where(jnp.isfinite(err), err, _BIG)


def select_best(params_all: jax.Array, errs: jax.Array) -> FitResult:
    """(..., T, 3) params + (..., T) errors -> argmin-selected FitResult."""
    errs = _finite_or_big(errs)
    best = jnp.argmin(errs, axis=-1).astype(jnp.int32)
    params = jnp.take_along_axis(params_all, best[..., None, None], axis=-2)[..., 0, :]
    error = jnp.take_along_axis(errs, best[..., None], axis=-1)[..., 0]
    return FitResult(best, params, error)


def select_predicted(
    params_all: jax.Array, errs: jax.Array, predicted_type: jax.Array
) -> FitResult:
    """(..., T, 3) params + (..., T) errors -> the tree-predicted type's fit."""
    pred = predicted_type.astype(jnp.int32)
    params = jnp.take_along_axis(params_all, pred[..., None, None], axis=-2)[..., 0, :]
    error = jnp.take_along_axis(_finite_or_big(errs), pred[..., None], axis=-1)[..., 0]
    return FitResult(pred, params, error)


def gather_rows(
    values: jax.Array, moments: dists.Moments, row_indices: jax.Array
) -> tuple[jax.Array, dists.Moments]:
    """Representative gather: the window's values rows plus every moment
    field at ``row_indices`` in one expression — a single executable when
    jitted (the per-field np round-trips used to dominate small grouped
    windows), and the prologue of the grouping-aware device dispatch."""
    return values[row_indices], jax.tree.map(lambda f: f[row_indices], moments)


def fit_all_rows(
    backend: "FitBackend",
    values: jax.Array,
    moments: dists.Moments,
    row_indices: jax.Array,
    types: Sequence[str],
    num_bins: int,
    mode: str = "fused",
) -> FitResult:
    """Algorithm 3 restricted to ``row_indices`` rows of the window (the
    grouping representatives): gather + fit as one computation.

    On the fused backend the gather rides into the kernel wrapper as a
    rep-indexed prologue (``kernels/fitpdf`` ``ops.fit_errors(row_indices=)``)
    so the compacted batch is produced inside the same launch that consumes
    it; other backends (and ``mode='faithful'``) gather with ``gather_rows``
    and run their ordinary ``fit_all``. Results are bitwise-identical either
    way — both paths run the same per-row ops on the same gathered rows.
    """
    if backend.name == "fused" and mode != "faithful":
        from repro.kernels.fitpdf import ops as fops

        sub_mom = jax.tree.map(lambda f: f[row_indices], moments)
        params_all = dists.fit_all(types, sub_mom)
        errs = fops.fit_errors(
            values, sub_mom, params_all, types, num_bins, row_indices=row_indices
        )
        return select_best(params_all, errs)
    sub_vals, sub_mom = gather_rows(values, moments, row_indices)
    return backend.fit_all(sub_vals, sub_mom, types, num_bins, mode)


def compute_pdf_and_error(
    values: jax.Array,
    moments: dists.Moments,
    types: Sequence[str],
    num_bins: int,
    mode: str = "fused",
    histogram_fn=None,
) -> FitResult:
    """Algorithm 3 for a batch of points: values (..., n) -> FitResult (...,).

    ``histogram_fn(values, vmin, vmax, num_bins)`` may be supplied to swap in
    the Pallas histogram kernel; defaults to the jnp scatter-add reference
    (the one-hot ``pe.histogram`` is kept as the test oracle only).
    """
    hist = histogram_fn or pe.histogram_scatter
    params_all = dists.fit_all(types, moments)  # (..., T, 3)
    edges = pe.interval_edges(moments.vmin, moments.vmax, num_bins)
    masses = pe.cdf_masses(types, params_all, edges)  # (..., T, L)

    if mode == "fused":
        freq = hist(values, moments.vmin, moments.vmax, num_bins)  # (..., L)
        errs = pe.pdf_error_from_freq(freq, masses)  # (..., T)
    elif mode == "faithful":
        # One histogram pass per candidate type — the paper's cost model
        # (its R subprocess re-reads the data for every candidate). XLA would
        # CSE the T identical passes away, so each pass reads the data through
        # a distinct optimization_barrier'd unit scale; the extra O(n) multiply
        # per type *is* the faithful per-type data pass.
        ones = jax.lax.optimization_barrier(jnp.ones((len(types),), values.dtype))
        per_type = []
        for t in range(len(types)):
            freq_t = hist(values * ones[t], moments.vmin, moments.vmax, num_bins)
            per_type.append(pe.pdf_error_from_freq(freq_t, masses[..., t, :]))
        errs = jnp.stack(per_type, axis=-1)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    return select_best(params_all, errs)


def compute_pdf_with_predicted_type(
    values: jax.Array,
    moments: dists.Moments,
    predicted_type: jax.Array,
    types: Sequence[str],
    num_bins: int,
    histogram_fn=None,
) -> FitResult:
    """Algorithm 4: fit only the tree-predicted type (one error pass).

    All T method-of-moments fits are O(1) scalar math per point, so we still
    stack them and select — the *expensive* part the paper saves (the per-type
    data pass / error evaluation) is done exactly once here.
    """
    hist = histogram_fn or pe.histogram_scatter
    params_all = dists.fit_all(types, moments)  # (..., T, 3)
    params = jnp.take_along_axis(
        params_all, predicted_type[..., None, None].astype(jnp.int32), axis=-2
    )[..., 0, :]

    edges = pe.interval_edges(moments.vmin, moments.vmax, num_bins)
    # Evaluate only the chosen type's CDF masses via a masked dense eval:
    # T is tiny and static, so computing each type's edge-CDF and selecting is
    # cheaper on TPU than a gather-of-functions; the O(n) histogram runs once.
    masses_all = pe.cdf_masses(types, params_all, edges)  # (..., T, L)
    masses = jnp.take_along_axis(
        masses_all, predicted_type[..., None, None].astype(jnp.int32), axis=-2
    )[..., 0, :]
    freq = hist(values, moments.vmin, moments.vmax, num_bins)
    error = _finite_or_big(pe.pdf_error_from_freq(freq, masses))
    return FitResult(predicted_type.astype(jnp.int32), params, error)


class FitBackend(NamedTuple):
    """One implementation of the per-window device work.

    ``moments`` maps values (..., n) -> Moments; ``histogram`` is the
    chain-path histogram_fn (also used by ``mode='faithful'``); ``fit_all``
    and ``fit_predicted`` are Algorithms 3 and 4.

    ``merge_stats``/``merge_hist`` are the streaming layer's pairwise
    sufficient-statistic and histogram-count merges (repro.streaming.moments)
    in the backend's own array module: host/float64 for ``reference``, jnp
    for the kernel backends. Same formulas either way — the registry carries
    them so incremental updates pick the path matching the backend that
    produced the stats.
    """

    name: str
    moments: Callable[[jax.Array], dists.Moments]
    histogram: Callable[..., jax.Array]
    fit_all: Callable[..., FitResult]  # (values, moments, types, num_bins, mode)
    fit_predicted: Callable[..., FitResult]  # (values, moments, pred, types, num_bins)
    merge_stats: Callable = None  # (SuffStats, SuffStats) -> SuffStats
    merge_hist: Callable = None  # (counts, counts) -> counts


@functools.lru_cache(maxsize=16)
def get_fit_backend(name: str = "fused", num_bins: int = 64) -> FitBackend:
    """Resolve a ``FIT_BACKENDS`` name; kernel imports stay lazy so the
    reference backend never touches Pallas."""
    # Lazy like the kernel imports: fitting must stay importable without
    # pulling the streaming subsystem in (and vice versa — streaming.moments
    # imports only distributions from core).
    from repro.streaming import moments as sm

    if name == "reference":
        hist = pe.histogram_scatter

        def fit_all(values, moments, types, num_bins, mode="fused"):
            return compute_pdf_and_error(
                values, moments, types, num_bins, mode=mode, histogram_fn=hist
            )

        def fit_predicted(values, moments, pred, types, num_bins):
            return compute_pdf_with_predicted_type(
                values, moments, pred, types, num_bins, histogram_fn=hist
            )

        return FitBackend(name, dists.moments_from_values, hist, fit_all,
                          fit_predicted, sm.merge_suffstats, sm.merge_counts)

    if name == "kernels":
        from repro.kernels.hist import ops as hops
        from repro.kernels.moments import ops as mops

        def fit_all(values, moments, types, num_bins, mode="fused"):
            return compute_pdf_and_error(
                values, moments, types, num_bins, mode=mode,
                histogram_fn=hops.histogram,
            )

        def fit_predicted(values, moments, pred, types, num_bins):
            return compute_pdf_with_predicted_type(
                values, moments, pred, types, num_bins, histogram_fn=hops.histogram
            )

        return FitBackend(name, mops.moments, hops.histogram, fit_all,
                          fit_predicted, sm.merge_suffstats_jnp,
                          sm.merge_counts_jnp)

    if name == "fused":
        from repro.kernels.fitpdf import ops as fops

        def moments_fn(values):
            return fops.moments(values, num_bins)

        def fit_all(values, moments, types, num_bins, mode="fused"):
            if mode == "faithful":
                # The paper's per-type pass structure cannot be a single
                # fused launch; keep the chain (scatter histogram per type).
                return compute_pdf_and_error(
                    values, moments, types, num_bins, mode=mode,
                    histogram_fn=pe.histogram_scatter,
                )
            params_all = dists.fit_all(types, moments)
            errs = fops.fit_errors(values, moments, params_all, types, num_bins)
            return select_best(params_all, errs)

        def fit_predicted(values, moments, pred, types, num_bins):
            params_all = dists.fit_all(types, moments)
            errs = fops.fit_errors(values, moments, params_all, types, num_bins)
            return select_predicted(params_all, errs, pred)

        return FitBackend(name, moments_fn, pe.histogram_scatter, fit_all,
                          fit_predicted, sm.merge_suffstats_jnp,
                          sm.merge_counts_jnp)

    raise ValueError(f"fit_backend must be one of {FIT_BACKENDS}, got {name!r}")
