"""Milliseconds the main thread waited for a run's first window, which no
compute overlaps: the program's ``pdf.load.first`` span, over its own count
(one a run, so one a slice opened), summed over the run calls
(``bench/spans.py``). A program without the span reads nothing."""

from bench.spans import ms_per


def read(ctx):
    return ms_per(ctx, ("pdf.load.first",), "pdf.load.first")
