"""Required bytes and operations of each kernel, from shapes alone.

One module per kernel, named as the kernel is, with
``required(**shape) -> (bytes, ops)``: what the computation needs to read
and write in HBM and to compute, whatever kernel does it, so a later
kernel doing the same work is read against the same count. ``least_time``
turns a count into the least time the chip could take.
"""

from __future__ import annotations


def least_time(nbytes: float, ops: float, peaks: dict) -> tuple[float, str]:
    """-> (seconds, bound): the larger of bytes over peak bandwidth and
    operations over peak FLOP/s, and which of the two it is."""
    t_mem = nbytes / peaks["bytes_per_s"]
    t_ops = ops / peaks["flops_per_s"]
    return (t_mem, "bytes") if t_mem >= t_ops else (t_ops, "ops")
