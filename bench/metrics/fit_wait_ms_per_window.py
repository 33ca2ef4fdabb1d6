"""Milliseconds per window spent waiting for the fit and copying its type,
parameters and error to the host: the program's ``pdf.fit.wait`` span, over
the ``windows`` counter, summed over the run calls (``bench/spans.py``)."""

from bench.spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "pdf.fit.wait")
