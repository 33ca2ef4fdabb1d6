"""Share of the windows whose file read landed in a host buffer the
executor took back from its free-list, in percent: the program's
``read_recycled`` counter (``pdf.load.read``) over ``windows``, summed over
the run calls (``bench/spans.py``). A program without the counter reads
nothing."""

from bench.spans import totals


def read(ctx):
    t = totals(ctx)
    if t is None:
        return None
    counters = t[1]
    if "read_recycled" not in counters or not counters.get("windows"):
        return None
    return 100.0 * counters["read_recycled"] / counters["windows"]
