"""Per-chip peaks and mesh builders (DESIGN.md section 5).

Defined as FUNCTIONS so importing this module never touches jax device
state; callers (dryrun.py) set XLA_FLAGS in their ``main`` before the
first jax init.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
from jax.sharding import AxisType

__all__ = [
    "Peaks", "PEAKS", "TARGET_KIND", "peaks", "make_production_mesh", "make_mesh",
]


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks (roofline denominators)."""

    peak_flops: float  # bf16 FLOP/s
    int8_ops: float  # int8 OP/s
    hbm_bw: float  # bytes/s
    hbm_bytes: int
    ici_bw: float  # bytes/s per ICI link
    source: str


_V5E = Peaks(
    peak_flops=197e12,
    int8_ops=393e12,
    hbm_bw=819e9,
    hbm_bytes=16 * 1024**3,
    # 1,600 Gbit/s of interconnect per chip over 4 links
    ici_bw=50e9,
    source='Google Cloud documentation, "TPU v5e"',
)

#: ``jax.Device.device_kind`` -> peaks (a v5e reports "TPU v5 lite").  A
#: device that is not here has no peaks: :func:`peaks` raises rather than
#: guessing.
PEAKS: Dict[str, Peaks] = {"TPU v5 lite": _V5E}

#: the chip that the production-mesh dry-run and the roofline model target
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind`` (``jax.devices()[0].device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Mesh for the training-parallel paths and tests.  Axes are ``Auto``:
    params are placed by ``models/sharding.py`` and XLA propagates the rest,
    which is what the train step, pipeline and collective-matmul code are
    written for (``jax.make_mesh`` defaults to ``Explicit`` axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
