"""Share of its roofline that the ``moments_edges_stats`` kernel reached:
the least time for the bytes and operations every window of the traced
window needs (``bench/roofline/moments_edges_stats.py``, at the peaks of
the run's device kind), over the summed device time of the kernel's
events. One kernel launch per window."""

from bench import trace as tracemod
from bench.roofline import least_time
from bench.roofline.moments_edges_stats import required

KERNEL = "moments_edges_stats"


def read(ctx):
    if ctx.trace is None:
        return None
    secs = tracemod.kernel_seconds(ctx.trace["trace"], ctx.chips, KERNEL)
    if secs <= 0:
        return None
    n, bins = ctx.cell.config["observations"], ctx.cell.config["num_bins"]
    nbytes = ops = 0.0
    for h in ctx.window.handed_back:
        b, o = required(points=h[3], observations=n, num_bins=bins)
        nbytes += b
        ops += o
    t, bound = least_time(nbytes, ops, ctx.peaks)
    ctx.notes[KERNEL] = {"kernel_s": secs, "least_s": t, "bound": bound,
                         "launches": len(tracemod.kernel_events(
                             ctx.trace["trace"], ctx.chips, KERNEL)),
                         "windows": len(ctx.window.handed_back)}
    return 100.0 * t / secs
