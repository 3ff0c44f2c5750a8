"""Mean time, in ms, a macro-batch spent resolving its handles (the
program's ``batch.resolve`` span: waiting for the server's lock, one
device slice per frame, the verdicts and counters), over the
macro-batches started in the window.  A resolve belongs to the ``batch``
span that holds it."""

import bisect


def read(ctx):
    t0, t1 = ctx.window
    batches = sorted((s["ts"], s["ts"] + s["dur"]) for s in ctx.spans
                     if s["name"] == "batch" and t0 <= s["ts"] < t1)
    starts = [a for a, _ in batches]
    durs = []
    for s in ctx.spans:
        if s["name"] != "batch.resolve":
            continue
        i = bisect.bisect_right(starts, s["ts"]) - 1
        if i >= 0 and s["ts"] + s["dur"] <= batches[i][1]:
            durs.append(s["dur"])
    return 1e3 * sum(durs) / len(durs) if durs else None
