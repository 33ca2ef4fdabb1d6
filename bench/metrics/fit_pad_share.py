"""Share of the rows launched into the fit that are padding, in percent:
1 - ``fit_rows`` / ``fit_rows_padded``, the program's counters of the
unpadded rows fitted and of the rows launched after ``padded_size``,
summed over the run calls (``bench/spans.py``)."""

from bench.spans import totals


def read(ctx):
    t = totals(ctx)
    if t is None:
        return None
    counters = t[1]
    padded = counters.get("fit_rows_padded", 0)
    if padded <= 0:
        return None
    return 100.0 * (1.0 - counters.get("fit_rows", 0) / padded)
