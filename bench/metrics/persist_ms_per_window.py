"""Persist-stage milliseconds per window (``.npz`` write and watermark, on
the writer thread): ``ExecutorReport.persist_seconds`` summed over the run
calls, over the windows they ran."""


def read(ctx):
    units = sum(r.windows for _d, r in ctx.window.calls)
    if units == 0:
        return None
    return 1000.0 * sum(r.persist_seconds for _d, r in ctx.window.calls) / units
