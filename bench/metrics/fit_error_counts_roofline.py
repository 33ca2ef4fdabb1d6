"""Share of its roofline that the ``fit_error_counts`` kernel reached: the
least time for the bytes and operations of the rows that needed a fit,
unpadded (``WindowStats.num_fitted``; ``bench/roofline/fit_error_counts.py``,
at the peaks of the run's device kind), over the summed device time of the
kernel's events, which run on the padded rows."""

from bench import trace as tracemod
from bench.roofline import least_time
from bench.roofline.fit_error_counts import required

KERNEL = "fit_error_counts"


def read(ctx):
    if ctx.trace is None:
        return None
    secs = tracemod.kernel_seconds(ctx.trace["trace"], ctx.chips, KERNEL)
    if secs <= 0:
        return None
    cfg = ctx.cell.config
    nbytes = ops = 0.0
    for h in ctx.window.handed_back:
        b, o = required(rows=h[5], observations=cfg["observations"],
                        num_types=len(cfg["types"]), num_bins=cfg["num_bins"])
        nbytes += b
        ops += o
    t, bound = least_time(nbytes, ops, ctx.peaks)
    ctx.notes[KERNEL] = {"kernel_s": secs, "least_s": t, "bound": bound,
                         "launches": len(tracemod.kernel_events(
                             ctx.trace["trace"], ctx.chips, KERNEL)),
                         "rows": sum(h[5] for h in ctx.window.handed_back)}
    return 100.0 * t / secs
