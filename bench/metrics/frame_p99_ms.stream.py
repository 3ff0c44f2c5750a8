"""99th percentile (nearest rank) of frame latency, in ms, over every frame
ready in the window, as the client saw it: from the frame's due time to
the moment the client holds the output (a frame that never came counts
with the time the client gave up).  A host that stands still for a tenth
of a second delays a dozen frames by that much, so this tail swings from
run to run with how often the host stalled."""

from yardstick.stats import percentile


def read(ctx):
    lat = ctx.driver.get("latency_ms")
    return percentile(lat, 99) if lat else None
