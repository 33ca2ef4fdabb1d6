"""Milliseconds per window of the representatives' gather, the tree's
predict and the fit's dispatch, on the main thread: the program's
``pdf.fit.launch`` span, over the ``windows`` counter, summed over the run
calls (``bench/spans.py``)."""

from bench.spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "pdf.fit.launch")
