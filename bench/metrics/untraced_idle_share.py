"""Share of the first chip's idle time inside the traced window during
which no ``pdf.*`` span of the program is open on the main thread, in
percent of that idle time (``bench/span_trace.py``): the idle time no step
of the program names."""

from bench import span_trace


def read(ctx):
    if ctx.trace is None:
        return None
    got = span_trace.untraced_idle(ctx.trace["trace"], ctx.chips)
    if got is None or got[0] <= 0:
        return None
    idle_s, untraced_s = got
    return 100.0 * untraced_s / idle_s
