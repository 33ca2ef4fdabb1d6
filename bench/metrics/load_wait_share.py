"""Share of the executor's wall time in which the compute stage waited for
the load stage (file read + host->device staging): sum of
``ExecutorReport.wait_seconds`` over sum of ``wall_seconds``, over every
run call of the window, in percent."""


def read(ctx):
    wall = sum(r.wall_seconds for _d, r in ctx.window.calls)
    if wall <= 0:
        return None
    return 100.0 * sum(r.wait_seconds for _d, r in ctx.window.calls) / wall
