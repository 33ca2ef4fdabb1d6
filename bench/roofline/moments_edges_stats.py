"""``moments_edges_stats``: one pass over a window's (P, n) float32
observations to per-point moments, min, max and the Eq.-5 edges.

Bytes: the P x n x 4 observations in, the (P, 8) stats and (P, L+1) edges
out. Operations per observation: the shift, d^2, d^3, d^4, four running
sums, min and max (10); per point, the finalize and the L+1 edges are
negligible and not counted.
"""

STATS = 8
OPS_PER_OBS = 10


def required(points: int, observations: int, num_bins: int) -> tuple[float, float]:
    nbytes = 4.0 * points * (observations + STATS + num_bins + 1)
    return nbytes, float(OPS_PER_OBS) * points * observations
