"""Mean time, in ms, from the moment a frame was ready to be sent (its
due time in an open loop) to the start of the macro-batch that served it,
for the frames ready in the window: the program's ``batch`` spans name the
request ids they served."""


def read(ctx):
    ready = ctx.driver["frame_ready"]
    t0, t1 = ctx.window
    waits = [
        s["ts"] - ready[r]
        for s in ctx.spans if s["name"] == "batch"
        for r in s["args"].get("rids", ()) if r in ready and t0 <= ready[r] < t1
    ]
    return 1e3 * sum(waits) / len(waits) if waits else None
