"""The whole step's share of the chip's bf16 peak, in %: operations the
pruned model requires for the real frames of the window's macro-batches,
over the device time of those macro-batches' compiled chunk programs
times the peak.  Taken over the batches, not the window, so it moves with
step speed at a fixed offered rate.  Real frames per batch come from the
server's counters; padding frames count no operations."""


def read(ctx):
    t0, t1 = ctx.window
    runs = ctx.trace.busiest_program_runs(t0, t1)
    st, size = ctx.driver["server_stats"], ctx.driver["batch_size"]
    if not runs or not st.get("batches"):
        return None
    real_per_batch = (st["batches"] * size - st["padded_frames"]) / st["batches"]
    ops = len(runs) * real_per_batch * ctx.driver["frame_ops"]
    return 100.0 * ops / (sum(m.dur for m in runs) * float(ctx.peaks["bf16_flops"]))
