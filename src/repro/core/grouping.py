"""Data grouping (§5.2): points sharing (quantized) mean/std fit once.

Three layers, mirroring how the paper's Spark shuffle decomposes on a TPU
mesh (DESIGN.md §2):

* ``quantize_keys``       — device: (mu, sigma) -> hi/lo int32 key columns,
  bit-exact with the host float64 Select path (``quantize_keys_host``).
* ``group_host``          — host: np.unique over a window's keys; returns the
  representative indices + inverse map. This is the honest analog of the
  paper's Aggregate: grouping is *data movement + dedup*, then the expensive
  fit runs only on representatives (real compute savings, since the host
  re-dispatches a smaller padded batch to the device).
* ``group_device_global`` — device: all_gather over the mesh + sort-based
  dedup, used by the dry-run to expose the *collective* cost of global
  grouping (the paper's "shuffle kills grouping at scale" finding shows up
  in the roofline's collective term).

Key semantics are unified: every path computes ``rint(x / tol)`` in float64
(the paper's 'acceptable fluctuation', §5.2). The host packs the quotient
into int64 columns; the device packs the same integer into (hi, lo) int32
column pairs — ``keys_to_int64`` converts between the two losslessly, so
host dedup, device dedup and the reuse cache all agree on what "the same
point" means for |quotient| < 2^63 of finite moments.
"""

from __future__ import annotations

import struct
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_TOL = 1e-6


# -- exact float64 lanes inside (possibly x64-disabled) traces -----------------
#
# The executor's jitted fns — and the dry-run's lowered step — are compiled
# with jax_enable_x64 off, where any *concrete* 64-bit constant captured by
# the trace is canonicalized down to 32 bits at lowering time (lowering runs
# outside any enable_x64 context, so ``jnp.float64(tol)`` silently becomes an
# f32 operand and the build fails or, worse, rounds). Ops recorded in the
# jaxpr keep their stated dtypes, so the rule is: 64-bit values may only be
# *derived by traced ops* — here, by bitcasting u32 words that are XORed with
# a traced u32 zero to tie them into the graph. Called eagerly on concrete
# arrays the same code simply executes in real f64 under the context.


def _traced_zero_u32(x: jax.Array) -> jax.Array:
    """A u32 zero that is a function of ``x`` (traced whenever x is)."""
    b = jax.lax.bitcast_convert_type(x.reshape(-1)[:1].astype(jnp.float32), jnp.uint32)
    return (b ^ b)[0]


def _exact_f64(x: float, zero_u32: jax.Array) -> jax.Array:
    """Embed the exact f64 scalar ``x`` via two u32 words (see note above)."""
    lo, hi = struct.unpack("<II", struct.pack("<d", float(x)))
    words = jnp.stack([zero_u32 ^ np.uint32(lo), zero_u32 ^ np.uint32(hi)])
    return jax.lax.bitcast_convert_type(words, jnp.float64)


def _hi_lo_i32(q64: jax.Array, two32: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Integer-valued f64 -> (hi, lo) int32 matching int64 ``q >> 32`` /
    ``q & 0xFFFFFFFF``. Pure f64 math (power-of-two scaling is exact for any
    f64 integer), so no int64 constants ever enter the trace."""
    hi_f = jnp.floor(q64 / two32)
    lo_f = q64 - hi_f * two32  # in [0, 2^32)
    hi = hi_f.astype(jnp.int32)
    lo = jax.lax.bitcast_convert_type(lo_f.astype(jnp.uint32), jnp.int32)
    return hi, lo


def quantize_keys(mean: jax.Array, std: jax.Array, tol: float = DEFAULT_TOL) -> jax.Array:
    """(P,) mu/sigma -> (P, 4) int32 keys ``[mu_hi, mu_lo, sig_hi, sig_lo]``.

    Bit-exact with the host Select path: the quotient ``rint(x / tol)`` is
    computed in true float64 (x64 lanes inside the surrounding trace) and
    split into hi/lo int32 words of its int64 value. This replaces the old
    mod-2^31 f32 fold, which aliased realistic seismic means (~3e3 at
    tol=1e-6 -> quotients ~3e9, past f32's 2^24 integer grid) into ~256-step
    buckets and went hash-like above int32 range — silently merging points
    whose statistics differ by far more than ``tol``. Exact for |quotient|
    < 2^63 of finite inputs (the same domain as the host int64 path).

    ``std`` is quantized as given; use :func:`quantize_keys_from_var` when
    only the variance is at hand (it reproduces the host's f64 sqrt).
    """
    with jax.enable_x64(True):
        # asarray inside the context: a float64 numpy input must stay f64
        # (outside, canonicalization would round it to f32 before the
        # widening — the aliasing class this function exists to eliminate).
        mean = jnp.asarray(mean)
        std = jnp.asarray(std)
        z = _traced_zero_u32(mean)
        t = _exact_f64(tol, z)
        two32 = _exact_f64(2.0**32, z)
        cols: list[jax.Array] = []
        for v in (mean, std):
            q = jnp.rint(v.astype(jnp.float64) / t)
            cols.extend(_hi_lo_i32(q, two32))
    return jnp.stack(cols, axis=-1)


def quantize_keys_from_var(
    mean: jax.Array, var: jax.Array, tol: float = DEFAULT_TOL
) -> jax.Array:
    """Quantize from (mean, var) exactly as the host Select path does:
    clamp, then sqrt in float64 (clamping commutes with the exact widening
    cast, and both paths' sqrt is correctly rounded f64)."""
    with jax.enable_x64(True):
        var = jnp.asarray(var)  # inside the context: f64 inputs stay f64
        # dtype-preserving zero built from a 32-bit literal (a 64-bit zero
        # constant would be canonicalized at an x64-off lowering)
        zero = jnp.asarray(0, jnp.int32).astype(var.dtype)
        std64 = jnp.sqrt(jnp.maximum(var, zero).astype(jnp.float64))
    return quantize_keys(mean, std64, tol)


def quantize_keys_host(
    mean: np.ndarray,
    var: np.ndarray,
    tol: float = DEFAULT_TOL,
    out: np.ndarray | None = None,
    tmp: np.ndarray | None = None,
) -> np.ndarray:
    """Host Select-path quantization: (P,) mean/var -> (P, 2) int64 keys.

    The promotion into the f64 scratch happens *before* the divide: numpy's
    NEP-50 loop selection computes ``np.divide(mean_f32, tol, out=f64)`` in
    float32 (the Python-float tol is weak), which silently re-introduced the
    f32-grid aliasing this path exists to avoid — casting first makes every
    op a genuine f64 loop. ``out``/``tmp`` let callers reuse buffers
    (one allocation per window size on the executor hot path)."""
    mean = np.asarray(mean)
    var = np.asarray(var)
    p = mean.shape[0]
    if out is None:
        out = np.empty((p, 2), dtype=np.int64)
    if tmp is None:
        tmp = np.empty((p,), dtype=np.float64)
    tmp[:] = mean  # exact f32 -> f64 widening
    np.divide(tmp, tol, out=tmp)
    np.rint(tmp, out=tmp)
    out[:, 0] = tmp
    tmp[:] = var
    np.maximum(tmp, 0.0, out=tmp)
    np.sqrt(tmp, out=tmp)
    np.divide(tmp, tol, out=tmp)
    np.rint(tmp, out=tmp)
    out[:, 1] = tmp
    return out


def quantize_features_host(
    mean: np.ndarray, std: np.ndarray, tol: float = DEFAULT_TOL
) -> np.ndarray:
    """(P,) mean/std -> (P, 2) int64 keys, for callers that already hold the
    standard deviation (the sampling path, Alg. 5 line 16). Same semantics
    as ``quantize_keys_host`` minus the var -> std derivation: widen to f64
    *before* the divide — the NEP-50 f32-loop trap applies here identically
    (``np.round(mean_f32 / tol)`` aliased on f32's 2^24 grid)."""
    mean = np.asarray(mean)
    std = np.asarray(std)
    out = np.empty((mean.shape[0], 2), dtype=np.int64)
    out[:, 0] = np.rint(mean.astype(np.float64) / tol)
    out[:, 1] = np.rint(std.astype(np.float64) / tol)
    return out


def keys_to_int64(keys: np.ndarray) -> np.ndarray:
    """(..., 2k) hi/lo int32 device keys -> (..., k) int64 host keys
    (the exact inverse of the hi/lo split; used for reuse-cache interop)."""
    k = np.asarray(keys)
    hi = k[..., 0::2].astype(np.int64)
    lo = k[..., 1::2].astype(np.int64) & 0xFFFFFFFF
    return (hi << 32) | lo


class HostGroups(NamedTuple):
    rep_indices: np.ndarray  # (G,) indices of one representative per group
    inverse: np.ndarray  # (P,) group id of every point
    num_groups: int


def group_host(keys: np.ndarray) -> HostGroups:
    """Window-level dedup on host (the shuffle boundary). keys: (P, C) int."""
    keys = np.asarray(keys)
    _, rep_indices, inverse = np.unique(
        keys, axis=0, return_index=True, return_inverse=True
    )
    return HostGroups(rep_indices.astype(np.int64), inverse.reshape(-1).astype(np.int64), len(rep_indices))


def padded_size(num: int, bucket: int = 256) -> int:
    """Smallest ``bucket * 2^k`` >= num (geometric jit-cache buckets)."""
    padded = bucket
    while padded < num:
        padded *= 2
    return padded


def pad_representatives(rep_indices: np.ndarray, bucket: int = 256) -> np.ndarray:
    """Pad the representative list to ``bucket * 2^k`` so the fit step's jit
    cache stays small across windows (padded slots repeat rep 0; their results
    are discarded by the inverse map).

    Geometric buckets bound the distinct padded shapes — and thus fit
    recompiles — to O(log P) per method instead of O(P/bucket), at the cost
    of at most 2x padding. Linear buckets made windows whose group count
    straddled a bucket edge trigger fresh XLA compiles mid-run (the
    fig06/4types grouping-slower-than-baseline inversion)."""
    g = len(rep_indices)
    out = np.full((padded_size(g, bucket),), rep_indices[0] if g else 0, dtype=np.int64)
    out[:g] = rep_indices
    return out


class DeviceGroups(NamedTuple):
    """Static-shape device grouping: every point learns its group's
    representative (the first point, in (key, index) sort order, holding an
    identical key).

    Contract for the sharded path (``group_device_global``): ``rep_for_point``
    and ``is_rep`` are *local-shard* slices (indices flattened across the
    shard-major gathered table), while ``num_groups`` is the *global* group
    count — summing ``is_rep`` on one shard counts only the groups whose
    representative lives there, and generally disagrees with ``num_groups``.
    ``num_groups_local`` is that per-shard count (sums to ``num_groups``
    across shards). For the single-shard ``group_device`` the two counts are
    equal by construction."""

    rep_for_point: jax.Array  # (P,) index of the point's representative
    is_rep: jax.Array  # (P,) bool
    num_groups: jax.Array  # () int32 — global group count
    num_groups_local: jax.Array  # () int32 — groups whose rep is on this shard


def group_device(keys: jax.Array) -> DeviceGroups:
    """Sort-based dedup with static shapes (single shard).

    Sorts by (*key columns, index), marks segment heads, and propagates
    each segment head's original index with a cumulative max — O(P log P),
    no dynamic shapes, fully jit-able. ``keys`` may have any number of
    integer columns; the exact path uses the (P, 4) hi/lo int32 pairs of
    ``quantize_keys``."""
    p = keys.shape[0]
    idx = jnp.arange(p, dtype=jnp.int32)
    # lexsort: last key is primary — index last so ties break by position.
    cols = tuple(keys[:, c] for c in reversed(range(keys.shape[-1])))
    order = jnp.lexsort((idx,) + cols)
    sk = keys[order]
    same_as_prev = jnp.concatenate(
        [jnp.array([False]), jnp.all(sk[1:] == sk[:-1], axis=-1)]
    )
    sorted_orig = order.astype(jnp.int32)
    # Segment head keeps its own index; followers inherit via cumulative max
    # (valid because within a segment the head has the smallest index only if
    # we seed followers with -1 and take a running max of head indices).
    head_idx = jnp.where(same_as_prev, -1, sorted_orig)
    seg_id = jnp.cumsum(jnp.logical_not(same_as_prev).astype(jnp.int32)) - 1
    # For each segment, the head value; scatter-max into (P,) segment table.
    seg_head = jnp.full((p,), -1, dtype=jnp.int32).at[seg_id].max(head_idx)
    rep_sorted = seg_head[seg_id]
    rep_for_point = jnp.zeros((p,), jnp.int32).at[order].set(rep_sorted)
    is_rep = rep_for_point == idx
    num = jnp.sum(is_rep).astype(jnp.int32)
    return DeviceGroups(rep_for_point, is_rep, num, num)


def group_device_global(keys: jax.Array, axis_names: tuple[str, ...]) -> DeviceGroups:
    """Global grouping across mesh axes — the paper's cross-node shuffle.

    all_gathers every shard's keys (this is the collective the roofline's
    collective term prices), dedups the gathered table, and maps each local
    point to its *global* representative index (flattened across shards).
    Call inside shard_map with ``axis_names`` bound.

    Returned counts follow the DeviceGroups contract: ``num_groups`` is the
    global count over the gathered table; ``num_groups_local`` counts the
    groups represented on *this* shard (``sum(is_rep)`` of the local slice),
    so per-shard callers tallying representatives agree with what they see.
    """
    gathered = keys
    for ax in axis_names:
        gathered = jax.lax.all_gather(gathered, ax, tiled=True)
    groups = group_device(gathered)
    # Local shard's slice of the global table:
    shard_index = 0
    total = 1
    for ax in axis_names:
        size = jax.lax.psum(1, ax)  # axis size (jax.lax.axis_size is newer jax)
        shard_index = shard_index * size + jax.lax.axis_index(ax)
        total *= size
    p_local = keys.shape[0]
    start = shard_index * p_local
    local_rep = jax.lax.dynamic_slice_in_dim(groups.rep_for_point, start, p_local)
    local_is_rep = jax.lax.dynamic_slice_in_dim(groups.is_rep, start, p_local)
    return DeviceGroups(
        local_rep,
        local_is_rep,
        groups.num_groups,
        jnp.sum(local_is_rep).astype(jnp.int32),
    )


def compact_representatives(
    rep_for_point: jax.Array, is_rep: jax.Array, padded_g: int
) -> tuple[jax.Array, jax.Array]:
    """Static-shape compaction of a DeviceGroups partition.

    Returns ``(gather_idx (padded_g,), point_slot (P,))``: ``gather_idx[:G]``
    are the representatives' original row indices in first-occurrence order
    (slots >= G fall back to row 0, discarded downstream) and ``point_slot``
    maps every point to its representative's slot — the device-side
    ``(rep_indices, inverse)`` pair, usable as gather/scatter indices inside
    one jitted launch. ``padded_g`` must be >= the partition's group count
    (out-of-range reps are silently dropped by the bounded scatter).
    """
    p = rep_for_point.shape[0]
    idx = jnp.arange(p, dtype=jnp.int32)
    rep_rank = jnp.cumsum(is_rep.astype(jnp.int32)) - 1  # slot of each rep
    slots = jnp.where(is_rep, rep_rank, padded_g)  # non-reps park in the sentinel
    gather_idx = jnp.zeros((padded_g + 1,), jnp.int32).at[slots].set(idx)[:padded_g]
    point_slot = rep_rank[rep_for_point]
    return gather_idx, point_slot


def scatter_group_results(
    rep_results: jax.Array, inverse: jax.Array
) -> jax.Array:
    """Representative results (G, ...) + inverse (P,) -> per-point (P, ...)."""
    return rep_results[inverse]
