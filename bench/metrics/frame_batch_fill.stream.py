"""Real frames per macro-batch, in % of the compiled batch size, from the
server's own counters over the window (``AsyncPlanServer.stats``)."""


def read(ctx):
    st, size = ctx.driver["server_stats"], ctx.driver["batch_size"]
    if not st.get("batches"):
        return None
    slots = st["batches"] * size
    return 100.0 * (slots - st["padded_frames"]) / slots
