"""Parity gates shared by the serving CLI and ``chip_smoke.py``.

On a CPU both sides of a comparison multiply in f32, so a served frame is
held to the direct run within :data:`FRAME_ATOL_CPU` and greedy tokens must
match exactly.  On a TPU, f32 matmuls and convs at the default precision --
XLA's and the Pallas kernels' alike -- contract in bf16 passes, so a served
result differs from an f32 reference by bf16 rounding: the served plan is
held to a reference run at ``"highest"`` precision within
:data:`FRAME_RTOL_TPU` of the frame's peak, and a greedy token may differ
only where the reference's top-2 logit margin is inside
:data:`LOGIT_ATOL_TPU`.  ``"highest"`` also reaches into the Pallas kernels
(f32 contraction), so a plan run under it is held to the reference within
:data:`EXACT_RTOL`: that gate, not the served one, catches a wrong kernel.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FRAME_ATOL_CPU",
    "FRAME_RTOL_TPU",
    "LOGIT_ATOL_TPU",
    "EXACT_RTOL",
    "EXACT_LOGIT_ATOL",
    "frame_error",
    "logit_tolerance",
    "rel_err",
    "ref_next_logits",
    "greedy_agreement",
]

#: served == direct on a CPU: f32 on both sides, batch-shape rounding only
FRAME_ATOL_CPU = 1e-5
#: served at the TPU's default precision vs "highest", relative to the
#: reference frame's peak.  XLA's own default-precision run of the
#: reference plans differs from "highest" by up to 2.2% of peak on the
#: three apps at 512x512 (style transfer; 1.1% coloring, 0.5% super
#: resolution, v5e): the bound leaves about 2x.
FRAME_RTOL_TPU = 5e-2
#: decoder logits at the TPU's default precision vs "highest": the 4-layer
#: qwen2.5-3b prefill differs by 0.051 at the last position (v5e); the
#: bound leaves about 2x
LOGIT_ATOL_TPU = 1e-1
#: any plan run under "highest" vs the reference under "highest", relative
#: to the peak: f32 summation order only (1e-6 measured on the v5e)
EXACT_RTOL = 1e-4
#: decoder logits under "highest" vs the jnp model under "highest"
EXACT_LOGIT_ATOL = 1e-3


def rel_err(got, want) -> float:
    """``max |got - want| / max |want|``."""
    got, want = np.asarray(got), np.asarray(want)
    peak = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / peak


def frame_error(got, want, platform: str) -> tuple:
    """``(error, bound)`` of a served frame against its reference: absolute
    against :data:`FRAME_ATOL_CPU` on a CPU, relative to the reference's
    peak against :data:`FRAME_RTOL_TPU` on a TPU."""
    if platform == "tpu":
        return rel_err(got, want), FRAME_RTOL_TPU
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    return err, FRAME_ATOL_CPU


def logit_tolerance(platform: str) -> float:
    """Top-2 margin inside which a greedy token may differ: none on a CPU."""
    return LOGIT_ATOL_TPU if platform == "tpu" else 0.0


@functools.lru_cache(maxsize=None)
def _jit_forward(cfg):
    from ..models.transformer import forward

    return jax.jit(lambda p, t: forward(p, cfg, t)[0])


def ref_next_logits(params, cfg, seqs: Sequence[Sequence[int]], pad_to: int) -> np.ndarray:
    """Next-token logits after each token list in ``seqs``, ``[len(seqs),
    vocab]``: one right-padded causal forward of the jnp model at highest
    precision (padding never reaches an earlier position)."""
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for j, s in enumerate(seqs):
        toks[j, : len(s)] = s
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(_jit_forward(cfg)(params, jnp.asarray(toks)))
    return np.stack([logits[j, len(s) - 1, : cfg.vocab] for j, s in enumerate(seqs)])


def greedy_agreement(params, cfg, prompts, served, tol: float) -> Dict[str, float]:
    """Hold ``served`` token lists to greedy decoding of the jnp model,
    teacher-forced on the served tokens.  Returns ``match``/``total`` token
    counts, ``near_ties`` (differ where the reference top-2 margin is
    ``<= tol``) and ``worst_miss`` (the widest margin among the other
    differences, 0.0 when there are none: the gate is ``worst_miss == 0``)."""
    seqs = [list(map(int, p)) for p in prompts]
    n_new = max(len(s) for s in served)
    pad_to = -(-(max(len(s) for s in seqs) + n_new) // 8) * 8
    match = total = near_ties = 0
    worst_miss = 0.0
    for t in range(n_new):
        live = [j for j, s in enumerate(served) if t < len(s)]
        ref = ref_next_logits(params, cfg, [seqs[j] for j in live], pad_to)
        for row, j in zip(ref, live):
            top2 = np.sort(row)[-2:]
            margin = float(top2[1] - top2[0])
            total += 1
            if served[j][t] == int(np.argmax(row)):
                match += 1
            elif margin <= tol:
                near_ties += 1
            else:
                worst_miss = max(worst_miss, margin)
            seqs[j].append(int(served[j][t]))
    return {"match": match, "total": total, "near_ties": near_ties,
            "worst_miss": worst_miss}
