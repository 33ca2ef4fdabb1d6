"""The program's host spans on the profiler clock, against the device's
idle time: how much of the first used chip's idle time inside the traced
window (``bench.window``) passes with no ``pdf.*`` span open on the main
thread (the host line ``bench/trace.py`` keeps), so that no step of the
program names what the host was doing."""

from __future__ import annotations

from bench import trace as tracemod

PREFIX = "pdf."


def _clip(intervals, lo: int, hi: int):
    for s, t in intervals:
        s, t = max(s, lo), min(t, hi)
        if t > s:
            yield s, t


def complement(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of ``[lo, hi)`` that the sorted disjoint ``intervals``
    leave free."""
    out, cur = [], lo
    for s, t in intervals:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def overlap(a, b) -> int:
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            total += t - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def untraced_idle(tr: tracemod.Trace, chips: int, prefix: str = PREFIX):
    """``(idle_s, untraced_s)``: the first used chip's idle seconds inside
    the window, and the part of them with no ``prefix`` span open on the
    main host line. ``None`` when the trace has no used chip or the main
    line holds no such span (a program without spans)."""
    names = tracemod.used_devices(tr, chips)
    spans = [(e.start, e.end) for e in tr.host if e.name.startswith(prefix)]
    if not names or not spans:
        return None
    lo, hi = tr.window
    busy = tracemod.union(_clip(((e.start, e.end) for e in tr.devices[names[0]]), lo, hi))
    idle = complement(busy, lo, hi)
    covered = tracemod.union(_clip(spans, lo, hi))
    idle_ns = sum(t - s for s, t in idle)
    return idle_ns / 1e9, (idle_ns - overlap(idle, covered)) / 1e9
