"""Milliseconds per window of the host Select (key quantization and
``np.unique`` over the window's points): the program's ``pdf.select`` span,
over the ``windows`` counter, summed over the run calls
(``bench/spans.py``)."""

from bench.spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "pdf.select")
