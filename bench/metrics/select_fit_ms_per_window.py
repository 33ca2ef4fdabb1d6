"""Compute-stage milliseconds per window after the moments kernel has
finished: host Select, the representative gather, the fit launch and their
syncs (``ExecutorReport.compute_seconds`` starts after the moments
kernel's ``block_until_ready``), summed over the run calls, over the
windows they ran."""


def read(ctx):
    units = sum(r.windows for _d, r in ctx.window.calls)
    if units == 0:
        return None
    return 1000.0 * sum(r.compute_seconds for _d, r in ctx.window.calls) / units
