"""Milliseconds per window of the file read on the prefetch thread: the
program's ``pdf.load.read`` span (``FileCubeSource.load_window``), over the
``windows`` counter, summed over the run calls (``bench/spans.py``)."""

from bench.spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "pdf.load.read")
