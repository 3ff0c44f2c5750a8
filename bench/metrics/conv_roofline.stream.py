"""Conv kernels' share of their roofline, in %: the least time the chip
could take for the window's convs over the device time of the conv
operations in the window.

Least time: every macro-batch runs each conv of the pruned model once over
the compiled batch (``counts.frame_convs``: kept input channels from the
config, not from the plan), each conv bounded by the larger of its
operations over the bf16 peak and its bytes over HBM bandwidth (memory,
for every conv of this model with float32 activations).  Device time: the
operations that are convs (``CONV_OPS``) -- the Pallas conv kernel, the
direct-GEMM 1x1 path and XLA's convolution fusions, which is what the
``lax.conv`` fallbacks compile to.  The layout copies around a fallback
are not counted."""

from yardstick import counts, trace

CONV_OPS = ("conv2d_gemm", "dense_matmul", "fusion:kOutput")


def read(ctx):
    t0, t1 = ctx.window
    spent = sum(e.dur for e in ctx.trace.ops_in(t0, t1) if trace.stable_name(e) in CONV_OPS)
    runs = len(ctx.trace.busiest_program_runs(t0, t1))
    if not spent or not runs:
        return None
    least = sum(counts.least_time(c["ops"], c["bytes"], ctx.peaks)[0] for c in ctx.driver["batch_convs"])
    return 100.0 * runs * least / spent
