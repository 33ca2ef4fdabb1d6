"""Pieces a window was staged in by the load stage: the program's
``load_pieces`` counter (``pdf.load.read``; 1 for a window staged whole)
over ``windows``, summed over the run calls (``bench/spans.py``). Says how
often the load reads a window in line pieces whose transfers overlap the
next piece's read. A program without the counter reads nothing."""

from bench.spans import totals


def read(ctx):
    t = totals(ctx)
    if t is None:
        return None
    counters = t[1]
    if "load_pieces" not in counters or not counters.get("windows"):
        return None
    return counters["load_pieces"] / counters["windows"]
