"""Milliseconds per window of the host-to-device copy of the read window,
on the prefetch thread: the program's ``pdf.load.h2d`` span, over the
``windows`` counter, summed over the run calls (``bench/spans.py``)."""

from bench.spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "pdf.load.h2d")
