"""The comparison arithmetic that decides ``correct``.

Kept here, not taken from the program (``repro.launch.parity`` holds the
program's own copy), so that a change to the program cannot change the
yardstick.  Each number compared is printed beside its limit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import numpy as np


@dataclasses.dataclass
class Check:
    """One number compared against its limit: it passes at or under the
    limit, or at or over it where ``at_least``."""

    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value >= self.limit if self.at_least else self.value <= self.limit

    def line(self) -> str:
        rel = ">=" if self.at_least else "<="
        verdict = "ok" if self.ok else "FAIL"
        return f"check {self.name} {self.value!r} limit {rel} {self.limit!r} {verdict}"


def prng_key(seed: int) -> jax.Array:
    """A JAX key from any whole-number seed (the driver's exceed 32 bits)."""
    word = np.random.default_rng([int(seed) & (2**63 - 1), 0]).integers(0, 2**31 - 1)
    return jax.random.PRNGKey(int(word))


def rel_err(got, want) -> float:
    """``max |got - want| / max |want|``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))


def rel_rms(got, want) -> float:
    """``||got - want|| / ||want||`` (2-norms over every element)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(float(np.linalg.norm(want)), 1e-30))


def frame_numbers(served, want, exact=None) -> Dict[str, float]:
    """The numbers a frame configuration's ``limits`` may name, each over a
    sample of frames: ``served`` against ``want`` (the reference at the
    precision the configuration states), lists in the same order, and
    ``exact`` (the float32 reference at ``"highest"``) where given.

    * ``frame_err``: the largest ``rel_err`` of a served frame;
    * ``frame_rms_err``: the largest ``rel_rms`` of a served frame;
    * ``frame_rms_ratio`` (with ``exact``): ``frame_rms_err`` over the same
      number of the exact reference against ``want``, so in units of what
      the stated precision's own rounding moves the output.  A program
      that rounds as stated reads well under 1, an exact one about 1, one
      that also rounds what it stores well over 1.
    """
    nan = float("nan")
    out = {
        "frame_err": max((rel_err(g, w) for g, w in zip(served, want)), default=nan),
        "frame_rms_err": max((rel_rms(g, w) for g, w in zip(served, want)), default=nan),
    }
    if exact is not None:
        scale = max((rel_rms(e, w) for e, w in zip(exact, want)), default=nan)
        out["frame_rms_ratio"] = out["frame_rms_err"] / scale if scale > 0 else nan
    return out


def check_same_tree(program: Any, ours: Any, what: str) -> None:
    """The benchmark's weights fit the program's parameter tree exactly:
    same names, same shapes, same dtypes."""
    def shapes(tree):
        return {jax.tree_util.keystr(k): (tuple(np.shape(v)), str(v.dtype))
                for k, v in jax.tree_util.tree_leaves_with_path(tree)}

    a, b = shapes(program), shapes(ours)
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise ValueError(f"{what}: the benchmark's weights do not fit the program's tree: {diff}")
