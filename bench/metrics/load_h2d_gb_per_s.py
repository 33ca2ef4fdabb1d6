"""Rate of the host-to-device copy of the read window, landing included, on
the prefetch thread, in GB/s (10^9 bytes): the program's ``bytes_h2d``
counter over the seconds of its ``pdf.load.h2d`` span, summed over the run
calls (``bench/spans.py``)."""

from bench.spans import totals


def read(ctx):
    t = totals(ctx)
    if t is None:
        return None
    spans, counters = t
    secs = spans.get("pdf.load.h2d", (0.0, 0))[0]
    if not counters.get("bytes_h2d") or secs <= 0:
        return None
    return counters["bytes_h2d"] / secs / 1e9
