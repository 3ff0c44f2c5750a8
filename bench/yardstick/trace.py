"""Reduce a profiler trace to what the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``; ``jax.profiler.ProfileData`` reads it.
On a TPU each chip is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line
holds one event per operation that ran on it, and whose ``XLA Modules``
line holds one event per program execution.  On a CPU, operations run on
host threads and carry an ``hlo_op`` stat; the reduction treats those as
the device, which is how the committed CPU trace tests it.

Host spans are put on the device trace's clock through one marker,
``bench.sync``, opened at a known ``time.perf_counter()`` reading.

The reduction gives: the union of operation intervals (busy time, idle
gaps), operation time by a stable name, program executions by module, and
each idle gap labelled by the innermost host span open across its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .stats import covered, gaps, merge

MARKER = "bench.sync"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # seconds on the host's perf_counter clock
    dur: float
    stats: Dict[str, str]

    @property
    def end(self) -> float:
        return self.start + self.dur


def latest_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


_KEEP_STATS = ("hlo_op", "hlo_module")


def _stats(ev) -> Dict[str, str]:
    out = {}
    try:
        for k, v in ev.stats:
            if k in _KEEP_STATS:
                out[k] = str(v)
    except Exception:  # an event without readable stats keeps none
        pass
    return out


@dataclasses.dataclass
class Trace:
    ops: List[Event]
    modules: List[Event]
    host: List[Event]  # the benchmark's own annotations (``bench.*``)

    def busy(self, lo: float, hi: float) -> float:
        """Seconds of ``[lo, hi]`` in which an operation ran (averaged over
        the chips that ran any)."""
        by_plane: Dict[str, List[Tuple[float, float]]] = {}
        for e in self.ops:
            by_plane.setdefault(e.stats.get("_plane", ""), []).append((e.start, e.end))
        if not by_plane:
            return 0.0
        return sum(covered(merge(iv), lo, hi) for iv in by_plane.values()) / len(by_plane)

    def idle_gaps(self, lo: float, hi: float) -> List[Tuple[float, float]]:
        return gaps(merge((e.start, e.end) for e in self.ops), lo, hi)

    def ops_in(self, lo: float, hi: float) -> List[Event]:
        return [e for e in self.ops if e.start >= lo and e.start < hi]

    def busiest_program_runs(self, lo: float, hi: float) -> List[Event]:
        """Executions, started in ``[lo, hi)``, of the program (module) that
        took the most device time there: a served plan's compiled chunk."""
        runs = [m for m in self.modules if lo <= m.start < hi]
        total: Dict[str, float] = {}
        for m in runs:
            total[m.name] = total.get(m.name, 0.0) + m.dur
        if not total:
            return []
        top = max(total, key=total.get)
        return [m for m in runs if m.name == top]


def load(logdir: str, marker_pc: float) -> Trace:
    """Read the newest trace under ``logdir``; ``marker_pc`` is the
    ``perf_counter()`` reading at which :data:`MARKER` was opened."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(latest_xplane(logdir))
    ops, modules, host = [], [], []
    device = [p for p in pd.planes if p.name.startswith("/device:")]
    marker_ns: Optional[float] = None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARKER and marker_ns is None:
                    marker_ns = ev.start_ns
    if marker_ns is None:
        raise ValueError(f"trace has no {MARKER} marker")
    shift = marker_pc - marker_ns * 1e-9

    def event(ev, plane_name) -> Event:
        st = _stats(ev)
        st["_plane"] = plane_name
        return Event(ev.name, ev.start_ns * 1e-9 + shift, ev.duration_ns * 1e-9, st)

    for plane in device:
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [event(e, plane.name) for e in line.events]
            elif line.name == "XLA Modules":
                modules += [event(e, plane.name) for e in line.events]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host.append(event(ev, plane.name))
                elif not device and ev.duration_ns > 0:
                    e = event(ev, plane.name)
                    if "hlo_op" in e.stats:
                        ops.append(e)
    return Trace(ops=ops, modules=modules, host=host)


_NUM = re.compile(r"(\.(\d+|clone))+$")
_HLO = re.compile(r"%([\w.-]+) = ")
_KIND = re.compile(r"kind=(k\w+)")


def stable_name(e: Event) -> str:
    """A name that survives recompiles.  On a TPU an operation's event is
    its HLO instruction (``%conv2d_gemm.13 = f32[...] custom-call(...)``):
    the instruction's name without XLA's numeric suffix -- a Pallas
    kernel's own name for a custom call -- and, for an unnamed fusion, its
    kind (``fusion:kOutput`` is XLA's convolution fusion).  On a CPU the
    event is named by its ``hlo_op``."""
    m = _HLO.match(e.name)
    if not m:
        return _NUM.sub("", e.stats.get("hlo_op", e.name))
    base = _NUM.sub("", m.group(1))
    kind = _KIND.search(e.name)
    return f"{base}:{kind.group(1)}" if base == "fusion" and kind else base


def time_by_name(events: Sequence[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for e in events:
        k = stable_name(e)
        out[k] = out.get(k, 0.0) + e.dur
    return out


def label_gaps(idle: Sequence[Tuple[float, float]], spans: Sequence[Tuple[str, float, float]]) -> List[Tuple[str, float]]:
    """``(label, seconds)`` of every gap: the innermost host span that is
    open at the gap's middle, or ``no_host_span``."""
    out = []
    for s, e in idle:
        mid = 0.5 * (s + e)
        best = None
        for name, a, b in spans:
            if a <= mid <= b and (best is None or b - a < best[2] - best[1]):
                best = (name, a, b)
        out.append((best[0] if best else "no_host_span", e - s))
    return out
