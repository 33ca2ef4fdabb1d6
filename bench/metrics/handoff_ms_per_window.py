"""Milliseconds per window of the hand-off to the persist stage on the main
thread: the moments' copy to the host, the scatter into the slice's
outputs, the writer's queue and the window callback. The program's
``pdf.handoff`` span, over the ``windows`` counter, summed over the run
calls (``bench/spans.py``)."""

from bench.spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "pdf.handoff")
