"""Milliseconds per window from the moments kernel's launch to its
``block_until_ready``, on the main thread: the program's ``pdf.moments``
span, over the ``windows`` counter, summed over the run calls
(``bench/spans.py``)."""

from bench.spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "pdf.moments")
