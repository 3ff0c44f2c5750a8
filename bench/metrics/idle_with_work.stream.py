"""Share of the window, in %, in which no operation ran on the device
while an admitted frame had not yet been dispatched: the trace's idle gaps
intersected with the union of the frames' waits.  A frame waits from the
end of its ``submit`` span (admitted) to the end of the ``chunk.call``
span of the ``batch`` span whose rids hold it (its chunk enqueued); a
frame whose chunk never came waits to the window's end.  At most
``device_idle.stream``: the rest of the idle time had nothing to run."""

import bisect

from yardstick.stats import merge


def read(ctx):
    t0, t1 = ctx.window
    admitted = {s["args"]["rid"]: s["ts"] + s["dur"] for s in ctx.spans
                if s["name"] == "submit" and "rid" in s["args"]}
    if not ctx.trace.ops or not admitted:
        return None
    batches = sorted((s["ts"], s["ts"] + s["dur"], s["args"].get("rids", ()))
                     for s in ctx.spans if s["name"] == "batch")
    starts = [b[0] for b in batches]
    dispatched = {}
    for s in ctx.spans:
        if s["name"] != "chunk.call":
            continue
        i = bisect.bisect_right(starts, s["ts"]) - 1
        if i >= 0 and s["ts"] + s["dur"] <= batches[i][1]:
            for r in batches[i][2]:
                dispatched[r] = s["ts"] + s["dur"]
    waits = merge((a, d) for a, d in ((a, dispatched.get(r, t1)) for r, a in admitted.items()) if a < d)
    idle = 0.0
    j = 0
    for s, e in ctx.trace.idle_gaps(t0, t1):  # both sorted and disjoint
        while j < len(waits) and waits[j][1] <= s:
            j += 1
        k = j
        while k < len(waits) and waits[k][0] < e:
            idle += min(e, waits[k][1]) - max(s, waits[k][0])
            k += 1
    return 100.0 * idle / (t1 - t0)
