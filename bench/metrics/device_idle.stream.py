"""Share of the window, in %, in which no operation ran on the device:
1 - union of the trace's operation intervals over the window."""


def read(ctx):
    t0, t1 = ctx.window
    if not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy(t0, t1) / (t1 - t0))
