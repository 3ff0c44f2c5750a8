"""The one traffic generator.

A mix is a data file, ``traffic/<mix>.json``.  Its ``arrivals`` key names
an arrival model, ``traffic/<arrivals>.py``, found by name as every other
file of a cell is; every other key is a parameter of the model or of the
system's client.  A model's ``schedule(p, seed, seconds)`` returns every
request of the window as a :class:`Request`, in the order the client sends
them.  Everything is drawn from the run's ``--seed`` and nothing else, so
the same seed gives the same schedule and inputs.

A request is ready at its ``due`` time (seconds from the window's start)
and, where ``after`` names an earlier request, once that one's answer is
in: an open loop leaves ``after`` at -1, a closed loop of ``n`` clients
sets it to ``i - n``.  Latency runs from the moment a request is ready.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due: float  # seconds from the window's start
    after: int  # index of the request whose answer it waits for, or -1
    item: int  # index into the client's pool of inputs
    stream: int = 0  # the client that sends it


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one purpose of one run: any whole-number seed (the
    driver's exceed 32 bits), one ``stream`` tuple per purpose."""
    return np.random.default_rng([int(seed) & (2**63 - 1), *stream])


def schedule(model, p: Dict[str, Any], seed: int, seconds: float) -> List[Request]:
    """``model.schedule`` for the mix ``p``, checked: due times in
    ``[0, seconds)`` and never decreasing, each ``after`` earlier than its
    request, each item in the client's pool."""
    reqs = list(model.schedule(p, seed, seconds))
    pool = int(p["pool"])
    for i, r in enumerate(reqs):
        if not 0.0 <= r.due < seconds or (i and r.due < reqs[i - 1].due):
            raise ValueError(f"request {i}: due {r.due} out of order or outside [0, {seconds})")
        if not -1 <= r.after < i or not 0 <= r.item < pool:
            raise ValueError(f"request {i}: after {r.after} or item {r.item} out of range")
    return reqs


def input_pool(p: Dict[str, Any], seed: int, shape) -> List[np.ndarray]:
    """The client's ``pool`` seeded inputs, host float32 arrays of ``shape``
    (a client holds its frames in host memory)."""
    g = rng(seed, 2)
    return [g.standard_normal(tuple(shape)).astype(np.float32) for _ in range(int(p["pool"]))]
