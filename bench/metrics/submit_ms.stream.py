"""Mean time, in ms, the client spent in ``AsyncPlanServer.submit`` (the
program's ``submit`` spans: the server's lock, the frame's copy to the
device, admission), over the frames that the window's macro-batches
served (the ``batch`` spans started in the window name their rids)."""


def read(ctx):
    t0, t1 = ctx.window
    served = {r for s in ctx.spans if s["name"] == "batch" and t0 <= s["ts"] < t1
              for r in s["args"].get("rids", ())}
    durs = [s["dur"] for s in ctx.spans if s["name"] == "submit" and s["args"].get("rid") in served]
    return 1e3 * sum(durs) / len(durs) if durs else None
