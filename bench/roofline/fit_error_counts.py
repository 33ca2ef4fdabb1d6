"""``fit_error_counts``: the Eq.-5 histogram of each fitted row and its L1
error against every candidate type's CDF masses.

Rows are the rows that needed a fit, unpadded. Bytes: rows x n x 4
observations, the (T, rows, L) masses, the rows' min and max in, and the
(rows, T) errors out. Operations per observation: subtract, scale, floor,
clip (2) and the bin count (6); per row and type, L subtractions,
absolute values and sums (3 L).
"""

OPS_PER_OBS = 6


def required(rows: int, observations: int, num_types: int,
             num_bins: int) -> tuple[float, float]:
    nbytes = 4.0 * rows * (observations + num_types * num_bins + 2 + num_types)
    ops = float(OPS_PER_OBS) * rows * observations + 3.0 * rows * num_types * num_bins
    return nbytes, ops
