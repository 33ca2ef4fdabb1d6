"""Pallas TPU kernels: the fused single-launch fit path.

Two kernels replace the chained moments -> histogram -> Eq.-5 reduction
device computations of ComputePDF&Error (Algorithms 3-4):

* ``moments_edges_stats`` — the streaming-moments kernel extended to also
  emit the Eq.-5 interval edges from its final min/max, so callers that
  need the bin geometry (persisted PDF descriptors, the standalone fused
  fit, tests) get it from the same single pass over the data.
* ``fit_error_counts`` — histogram + error: streams the raw window once,
  accumulates the ``(bp, L)`` frequency block in a VMEM scratch, and —
  with that block still resident — the last obs-chunk's epilogue reduces
  the Eq.-5 L1 error against every candidate type's CDF masses. Only the
  ``(P, T)`` error matrix reaches HBM: the ``(P, n, L)`` one-hot and the
  ``(P, L)`` frequency round-trip of the chained path never exist. The
  ``(T, P, L)`` masses ride along as an *input* (T*L/n of the window's
  bytes, ~0.3 MB of a 25 MB Set1 window), computed by the jitted wrapper
  in XLA with the reference ``pdf_error.cdf_masses``. The CDFs need
  ``erf`` (normal, lognormal) and the incomplete gamma/beta functions
  (10 types), none of which Mosaic lowers; evaluating them outside keeps
  the kernel free of every special function, and keeps its masses
  bit-identical to the reference backend's (f32 ``gammainc`` at the huge
  shape parameters the gamma fitter produces for near-normal windows
  amplifies 1 ulp of edge into ~5e-2 of Eq.-5 error). The masses are laid
  out type-major so the epilogue reads each type's ``(bp, L)`` tile by a
  leading-axis index.

The histogram accumulation strategy is a static switch: compare-and-sum
one-hot for the Mosaic TPU path (same scheme as kernels/hist), and a
rank-decomposed matmul for interpret/CPU — ``freq[a, b] = sum_n
onehot_hi[n, a] * onehot_lo[n, b]`` with ``bin = a * B + b`` — which
contracts on the (multi-threaded) XLA dot path instead of the L-wide
one-hot or XLA CPU's single-threaded scatter (~4.6x faster than scatter
at L=64; counts are exact integer sums either way). Grid layout matches
the moments kernel: (P/bp, n/bn) with the obs-chunk axis innermost
(sequential on TPU) so VMEM accumulators carry across chunks of a point
tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_STATS = 8  # mean, var(unbiased), skew, kurt, min, max, (2 pad lanes)
_EPS = 1e-12


def _moments_edges_kernel(
    n_valid: int, num_bins: int, x_ref, stats_ref, edges_ref, acc_ref, shift_ref
):
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    bp, bn = x_ref.shape

    x = x_ref[...].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (bp, bn), 1) + j * bn
    valid = col < n_valid

    @pl.when(j == 0)
    def _init():
        # Shift = first observation of each point (any in-range value works);
        # kills the float32 cancellation of raw power sums.
        shift_ref[...] = x[:, 0:1]
        acc_ref[...] = jnp.zeros_like(acc_ref)

    shift = shift_ref[...]  # (bp, 1)
    d = jnp.where(valid, x - shift, 0.0)
    big = jnp.float32(3.4e38)
    xmin = jnp.min(jnp.where(valid, x, big), axis=1)
    xmax = jnp.max(jnp.where(valid, x, -big), axis=1)

    acc = acc_ref[...]
    s1 = acc[:, 0] + jnp.sum(d, axis=1)
    s2 = acc[:, 1] + jnp.sum(d * d, axis=1)
    s3 = acc[:, 2] + jnp.sum(d * d * d, axis=1)
    s4 = acc[:, 3] + jnp.sum(d * d * d * d, axis=1)
    mn = jnp.where(j == 0, xmin, jnp.minimum(acc[:, 4], xmin))
    mx = jnp.where(j == 0, xmax, jnp.maximum(acc[:, 5], xmax))
    acc_ref[...] = jnp.stack([s1, s2, s3, s4, mn, mx, s1, s1], axis=1)

    @pl.when(j == nj - 1)
    def _finalize():
        n = jnp.float32(n_valid)
        md = s1 / n  # mean of shifted values
        m2 = jnp.maximum(s2 / n - md * md, 0.0)
        m3 = s3 / n - 3.0 * md * (s2 / n) + 2.0 * md**3
        m4 = s4 / n - 4.0 * md * (s3 / n) + 6.0 * md * md * (s2 / n) - 3.0 * md**4
        mean = shift[:, 0] + md
        var = m2 * n / jnp.maximum(n - 1.0, 1.0)
        sig = jnp.sqrt(jnp.maximum(m2, 1e-12))
        skew = m3 / sig**3
        kurt = m4 / jnp.maximum(m2, 1e-12) ** 2 - 3.0
        stats_ref[...] = jnp.stack(
            [mean, var, skew, kurt, mn, mx, jnp.zeros_like(mean), jnp.zeros_like(mean)],
            axis=1,
        )
        # Eq.-5 interval edges, same formula as pdf_error.interval_edges.
        span = jnp.maximum(mx - mn, _EPS)
        k = jax.lax.broadcasted_iota(jnp.int32, (bp, num_bins + 1), 1).astype(
            jnp.float32
        )
        edges_ref[...] = mn[:, None] + span[:, None] * k / num_bins


@functools.partial(
    jax.jit, static_argnames=("num_bins", "block_points", "block_obs", "interpret")
)
def moments_edges_stats(
    values: jax.Array,
    num_bins: int,
    block_points: int = 8,
    block_obs: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """values (P, n) -> (stats (P, NUM_STATS), edges (P, L+1)) f32.
    P % bp == 0 required (ops.py pads); n is masked in-kernel."""
    p, n = values.shape
    bp = min(block_points, p)
    bn = min(block_obs, max(128, 128 * ((n + 127) // 128)))
    grid = (p // bp, -(-n // bn))
    n_padded = grid[1] * bn
    if n_padded != n:
        values = jnp.pad(values, ((0, 0), (0, n_padded - n)))

    return pl.pallas_call(
        functools.partial(_moments_edges_kernel, n, num_bins),
        grid=grid,
        in_specs=[pl.BlockSpec((bp, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bp, NUM_STATS), lambda i, j: (i, 0)),
            pl.BlockSpec((bp, num_bins + 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, NUM_STATS), jnp.float32),
            jax.ShapeDtypeStruct((p, num_bins + 1), jnp.float32),
        ],
        scratch_shapes=[
            # VMEM accumulators persist across the sequential obs-chunk axis.
            pltpu.VMEM((bp, NUM_STATS), jnp.float32),
            pltpu.VMEM((bp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(values)


def _fit_error_kernel(
    n_valid: int,
    num_bins: int,
    num_types: int,
    matmul_hist: bool,
    x_ref,
    lo_ref,
    hi_ref,
    masses_ref,
    err_ref,
    freq_ref,
):
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    bp, bn = x_ref.shape

    @pl.when(j == 0)
    def _init():
        freq_ref[...] = jnp.zeros_like(freq_ref)

    x = x_ref[...].astype(jnp.float32)
    lo = lo_ref[...]  # (bp, 1)
    hi = hi_ref[...]
    span = jnp.maximum(hi - lo, _EPS)

    col = jax.lax.broadcasted_iota(jnp.int32, (bp, bn), 1) + j * bn
    valid = col < n_valid
    idx = jnp.floor((x - lo) / span * num_bins)
    idx = jnp.clip(idx, 0, num_bins - 1).astype(jnp.int32)

    if matmul_hist:
        # Interpret/CPU: decompose bin = a*B + b and contract the two narrow
        # one-hots over the obs axis on the dot path. Padding columns carry
        # idx = -1: floor-div gives a = -1 (matches no hi slot), so they
        # contribute nothing.
        idx = jnp.where(valid, idx, -1)
        b_width = min(16, num_bins)
        a_width = -(-num_bins // b_width)
        hi = (
            idx[:, :, None] // b_width
            == jax.lax.broadcasted_iota(jnp.int32, (1, 1, a_width), 2)
        ).astype(jnp.float32)
        lo_bits = (
            idx[:, :, None] % b_width
            == jax.lax.broadcasted_iota(jnp.int32, (1, 1, b_width), 2)
        ).astype(jnp.float32)
        counts = jnp.einsum("pna,pnb->pab", hi, lo_bits)
        freq_ref[...] += counts.reshape(bp, a_width * b_width)[:, :num_bins]
    else:
        # Mosaic TPU: dense compare-and-sum (no scatter support); padding
        # columns vote for bin -1 => match nothing.
        idx = jnp.where(valid, idx, -1)
        bins = jax.lax.broadcasted_iota(jnp.int32, (1, 1, num_bins), 2)
        onehot = (idx[:, :, None] == bins).astype(jnp.float32)  # (bp, bn, L)
        freq_ref[...] += jnp.sum(onehot, axis=1)

    @pl.when(j == nj - 1)
    def _epilogue():
        # Frequency block still VMEM-resident: reduce the Eq.-5 error
        # against each candidate type's (bp, L) masses tile.
        rel = freq_ref[...] / jnp.float32(max(n_valid, 1))  # (bp, L)
        errs = [
            jnp.sum(jnp.abs(rel - masses_ref[t]), axis=1) for t in range(num_types)
        ]
        err_ref[...] = jnp.stack(errs, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("num_bins", "block_points", "block_obs", "interpret", "matmul_hist"),
)
def fit_error_counts(
    values: jax.Array,
    vmin: jax.Array,
    vmax: jax.Array,
    masses: jax.Array,
    num_bins: int,
    block_points: int = 8,
    block_obs: int = 512,
    interpret: bool = False,
    matmul_hist: bool = False,
) -> jax.Array:
    """values (P, n), vmin/vmax (P,), masses (T, P, L) -> Eq.-5 errors
    (P, T). P % block_points == 0 required (ops.py pads); n masked
    in-kernel."""
    p, n = values.shape
    t = masses.shape[0]
    bp = min(block_points, p)
    bn = min(block_obs, max(128, 128 * ((n + 127) // 128)))
    grid = (p // bp, -(-n // bn))
    n_padded = grid[1] * bn
    if n_padded != n:
        values = jnp.pad(values, ((0, 0), (0, n_padded - n)))

    return pl.pallas_call(
        functools.partial(_fit_error_kernel, n, num_bins, t, matmul_hist),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bp, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bp, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((t, bp, num_bins), lambda i, j: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((bp, t), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((p, t), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bp, num_bins), jnp.float32)],
        interpret=interpret,
    )(
        values,
        vmin.reshape(p, 1).astype(jnp.float32),
        vmax.reshape(p, 1).astype(jnp.float32),
        masses.astype(jnp.float32),
    )
