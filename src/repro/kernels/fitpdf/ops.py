"""Jitted wrappers for the fused fit kernels: padding, block/backend dispatch.

Block defaults are per execution mode: interpret (CPU) wants few, large grid
cells — the interpreter's per-cell overhead dominates, and the matmul-
decomposed histogram accumulation beats both the L-wide one-hot and XLA
CPU's scatter — while the Mosaic TPU path keeps VMEM-sized tiles and the
one-hot scheme. ``benchmarks/kernel_bench.py`` audits the TPU tile bytes
against the 16 MiB/core VMEM budget.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import pdf_error as pe
from repro.core.distributions import Moments
from repro.kernels.fitpdf.kernel import fit_error_counts, moments_edges_stats

# Interpret mode: few big cells + matmul accumulation (measured on CPU).
INTERP_BLOCK_POINTS, INTERP_BLOCK_OBS = 64, 4096
# Mosaic TPU: VMEM-sized tiles + one-hot accumulation.
TPU_BLOCK_POINTS, TPU_BLOCK_OBS = 8, 512


def _dispatch(interpret: bool | None, block_points: int | None, block_obs: int | None):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if block_points is None:
        block_points = INTERP_BLOCK_POINTS if interpret else TPU_BLOCK_POINTS
    if block_obs is None:
        block_obs = INTERP_BLOCK_OBS if interpret else TPU_BLOCK_OBS
    return interpret, block_points, block_obs


def _pad_rows(flat: jax.Array, bp: int) -> jax.Array:
    pad = (-flat.shape[0]) % bp
    if pad:
        flat = jnp.concatenate([flat, flat[-1:].repeat(pad, axis=0)], axis=0)
    return flat


def moments_and_edges(
    values: jax.Array,
    num_bins: int,
    block_points: int | None = None,
    block_obs: int | None = None,
    interpret: bool | None = None,
) -> tuple[Moments, jax.Array]:
    """(..., n) -> (Moments, edges (..., L+1)): one pass over the data."""
    interpret, block_points, block_obs = _dispatch(interpret, block_points, block_obs)
    shape = values.shape
    flat = values.reshape(-1, shape[-1])
    p = flat.shape[0]
    bp = min(block_points, max(1, p))
    flat = _pad_rows(flat, bp)
    stats, edges = moments_edges_stats(
        flat, num_bins, block_points=bp, block_obs=block_obs, interpret=interpret
    )
    lead = shape[:-1]
    m = Moments(*(stats[:p, i].reshape(lead) for i in range(6)))
    return m, edges[:p].reshape(lead + (num_bins + 1,))


def moments(
    values: jax.Array,
    num_bins: int = 64,
    block_points: int | None = None,
    block_obs: int | None = None,
    interpret: bool | None = None,
) -> Moments:
    """(..., n) -> Moments via the extended kernel (edges discarded)."""
    return moments_and_edges(
        values, num_bins, block_points=block_points, block_obs=block_obs,
        interpret=interpret,
    )[0]


def fit_errors(
    values: jax.Array,
    moments: Moments,
    params_all: jax.Array,
    types: tuple[str, ...],
    num_bins: int,
    edges: jax.Array | None = None,
    block_points: int | None = None,
    block_obs: int | None = None,
    interpret: bool | None = None,
    row_indices: jax.Array | None = None,
) -> jax.Array:
    """(..., n) values + (..., T, 3) params -> (..., T) Eq.-5 errors.

    Single launch over the data: the histogram never reaches HBM, and the
    Eq.-5 reduction runs in the kernel epilogue while the frequency block
    is still VMEM-resident. The CDF masses at the edges are evaluated here
    in XLA (``pe.cdf_masses``, the reference formula) and enter the kernel
    as a small ``(T, P, L)`` input — see kernel.py on why no CDF runs in
    the kernel. ``edges`` defaults to ``pe.interval_edges``; pass the
    moments kernel's emitted edges to chain the two launches.

    ``row_indices`` (1-D, optional) is the rep-indexed gather prologue of
    the grouping-aware dispatch: ``values`` stays the *full* window while
    ``moments`` / ``params_all`` / ``edges`` are already per-representative
    (leading dims == ``row_indices.shape``); the representatives' value rows
    are gathered here, inside the same jitted computation as the kernel, so
    the compacted batch is produced by the launch that consumes it instead
    of bouncing through a host re-dispatch. Bitwise-identical to calling
    with pre-gathered ``values[row_indices]``.
    """
    interpret, block_points, block_obs = _dispatch(interpret, block_points, block_obs)
    t = len(types)
    if edges is None:
        edges = pe.interval_edges(moments.vmin, moments.vmax, num_bins)
    masses = pe.cdf_masses(types, params_all, edges)  # (..., T, L)
    if row_indices is not None:
        values = values.reshape(-1, values.shape[-1])[row_indices]
    shape = values.shape
    flat = values.reshape(-1, shape[-1])
    p = flat.shape[0]
    bp = min(block_points, max(1, p))
    flat = _pad_rows(flat, bp)
    flo = _pad_rows(moments.vmin.reshape(-1, 1), bp)
    fhi = _pad_rows(moments.vmax.reshape(-1, 1), bp)
    fmass = _pad_rows(masses.reshape(-1, t, num_bins), bp).transpose(1, 0, 2)
    errs = fit_error_counts(
        flat, flo, fhi, fmass, num_bins,
        block_points=bp, block_obs=block_obs, interpret=interpret,
        matmul_hist=interpret,
    )
    return errs[:p].reshape(shape[:-1] + (t,))
