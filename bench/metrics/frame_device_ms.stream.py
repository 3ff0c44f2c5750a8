"""Device busy time per macro-batch, in ms: the union of the trace's
operation intervals over the window, over the macro-batches that started
in it (the program's ``batch`` spans)."""


def read(ctx):
    t0, t1 = ctx.window
    n = sum(1 for s in ctx.spans if s["name"] == "batch" and t0 <= s["ts"] < t1)
    if not n or not ctx.trace.ops:
        return None
    return 1e3 * ctx.trace.busy(t0, t1) / n
