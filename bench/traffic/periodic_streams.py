"""Arrival model: independent fixed-rate streams (video), open loop.

Keys of the mix: ``streams``, ``fps``, ``pool`` (inputs cycled from the
client's seeded pool), ``phase_jitter`` (each stream's phase lies in its
own slot of ``1/(streams*fps)``; 1.0 draws it anywhere in the slot, 0
spaces the streams evenly).  Every seed sends the same number of streams
and frames, only in another phase and order of inputs; with
``phase_jitter`` 0 every seed sends frames at the same moments, and only
which stream and which input each one is differ.
"""

from yardstick.traffic import Request, rng


def schedule(p, seed, seconds):
    n, period = int(p["streams"]), 1.0 / float(p["fps"])
    g = rng(seed, 1)
    slots = g.permutation(n)
    phases = (slots + float(p.get("phase_jitter", 1.0)) * g.random(n)) / n * period
    frames = sorted(
        (float(phases[s] + k * period), s)
        for s in range(n)
        for k in range(int((seconds - phases[s]) / period) + 1)
        if phases[s] + k * period < seconds
    )
    items = g.integers(0, int(p["pool"]), size=len(frames))
    return [Request(due, -1, int(i), s) for (due, s), i in zip(frames, items)]
