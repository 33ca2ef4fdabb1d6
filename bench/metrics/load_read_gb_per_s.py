"""Rate of the file read on the prefetch thread, in GB/s (10^9 bytes): the
program's ``bytes_read`` counter over the seconds of its ``pdf.load.read``
span, summed over the run calls (``bench/spans.py``). Unlike milliseconds a
window, it compares windows of different sizes."""

from bench.spans import totals


def read(ctx):
    t = totals(ctx)
    if t is None:
        return None
    spans, counters = t
    secs = spans.get("pdf.load.read", (0.0, 0))[0]
    if not counters.get("bytes_read") or secs <= 0:
        return None
    return counters["bytes_read"] / secs / 1e9
