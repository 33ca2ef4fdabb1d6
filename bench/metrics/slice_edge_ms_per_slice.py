"""Milliseconds per slice at a slice's edges on the main thread: its
opening (persist stage, outputs, prefetcher start; ``pdf.slice.open``) and
its drain (prefetcher close, persist flush, results; ``pdf.slice.drain``),
over the slices opened, summed over the run calls (``bench/spans.py``)."""

from bench.spans import ms_per


def read(ctx):
    return ms_per(ctx, ("pdf.slice.open", "pdf.slice.drain"), "pdf.slice.open")
