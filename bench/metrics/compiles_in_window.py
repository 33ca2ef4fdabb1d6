"""Backend compiles (persistent-cache hits included) between the first
timed ``PDFSession.run`` call and the end of the last: the program's
``runtime.cluster.compile_counters`` delta. Should read 0."""


def read(ctx):
    return float(ctx.window.compile_delta["compiles"])
