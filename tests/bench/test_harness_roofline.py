"""Exact required bytes and operations at Set1 shapes, and the peaks table."""

import pytest

from bench import harness
from bench.roofline import fit_error_counts, least_time, moments_edges_stats

V5E = harness.peaks_for("TPU v5 lite")


def test_moments_counts_at_a_set1_window():
    nbytes, ops = moments_edges_stats.required(points=6275, observations=1000, num_bins=20)
    assert nbytes == 4 * 6275 * (1000 + 8 + 21) == 25_827_900
    assert ops == 10 * 6275 * 1000
    t, bound = least_time(nbytes, ops, V5E)
    assert bound == "bytes" and t == pytest.approx(25_827_900 / 819e9)


@pytest.mark.parametrize("rows,types,want_bytes,want_ops", [
    (818, 4, 4 * 818 * (1000 + 80 + 2 + 4), 6 * 818 * 1000 + 3 * 818 * 4 * 20),
    (6275, 10, 4 * 6275 * (1000 + 200 + 2 + 10), 6 * 6275 * 1000 + 3 * 6275 * 10 * 20),
])
def test_fit_counts_use_unpadded_rows(rows, types, want_bytes, want_ops):
    nbytes, ops = fit_error_counts.required(rows=rows, observations=1000,
                                            num_types=types, num_bins=20)
    assert (nbytes, ops) == (want_bytes, want_ops)
    assert least_time(nbytes, ops, V5E)[1] == "bytes"


def test_peaks_are_v5e_published_and_unknown_kinds_fail():
    assert V5E["flops_per_s"] == 197e12 and V5E["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
