"""The program's named host spans and work counters, as the per-layer
metrics read them: ``SessionReport.spans`` ({name: (seconds, count)}) and
``SessionReport.counters`` ({name: count}), summed over the run calls of
the measured window. A program whose reports carry no spans reads
``None``, so each such metric is left out of its result line."""


def totals(ctx):
    """``(spans, counters)`` summed over the window's calls, or ``None``."""
    spans: dict = {}
    counters: dict = {}
    if not ctx.window.calls:
        return None
    for _d, report in ctx.window.calls:
        rs, rc = getattr(report, "spans", None), getattr(report, "counters", None)
        if rs is None or rc is None:
            return None
        for name, (secs, n) in rs.items():
            s0, n0 = spans.get(name, (0.0, 0))
            spans[name] = (s0 + secs, n0 + n)
        for name, v in rc.items():
            counters[name] = counters.get(name, 0) + v
    return spans, counters


def ms_per(ctx, names, per):
    """Milliseconds of the spans ``names`` together, over ``per``: a counter
    name, or a span name whose count is the divisor."""
    t = totals(ctx)
    if t is None:
        return None
    spans, counters = t
    if not any(n in spans for n in names):
        return None
    div = counters.get(per) if per in counters else spans.get(per, (0.0, 0))[1]
    if not div:
        return None
    return 1000.0 * sum(spans.get(n, (0.0, 0))[0] for n in names) / div


def ms_per_window(ctx, name):
    return ms_per(ctx, (name,), "windows")
