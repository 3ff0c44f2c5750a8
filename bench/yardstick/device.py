"""The device a run measures: what JAX reports, the published peaks, and
the peak memory of the fullest chip.

A measurement never falls back to the CPU: :func:`require` raises when JAX
finds no TPU or fewer chips than the cell asks for, and :func:`peaks`
raises for a device kind that is not in ``peaks.json``.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

import jax

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell needs."""


def describe() -> Dict[str, Any]:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": jax.device_count()}


def require(chips: int) -> Dict[str, Any]:
    dev = describe()
    if dev["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX runs on {dev['platform']!r}")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {dev['count']}")
    return dev


def peaks(kind: str) -> Dict[str, Any]:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    """``peak_bytes_in_use`` of the fullest of the first ``chips`` devices
    (0 where the backend keeps no such statistic, as the CPU's)."""
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
