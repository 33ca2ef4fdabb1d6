"""Milliseconds per run call of the session's construction (source, spec
hash, compile cache; ``pdf.session.open``) and its executor's
(``pdf.executor.build``), over the sessions opened, summed over the run
calls (``bench/spans.py``)."""

from bench.spans import ms_per


def read(ctx):
    return ms_per(ctx, ("pdf.session.open", "pdf.executor.build"), "pdf.session.open")
