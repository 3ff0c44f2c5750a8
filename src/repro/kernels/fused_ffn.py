"""Fused gated-FFN (SwiGLU/GeGLU) first half: ``act(x@Wg) * (x@Wu)``.

The DSL-fusion pass (paper section 3) merges elementwise ops into their GEMM
producer; for gated FFNs two GEMMs share the same x tile, so one kernel pass
streams x once, keeps *two* VMEM accumulators, and applies the gate without
materializing either projection in HBM -- halving x traffic and removing two
HBM round-trips for the [M, F] intermediates.

Grid ``(M/bm, F/bn, K/bk)``; Wg/Wu blocks ride the same (k, j) schedule.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dense_matmul import _ACTIVATIONS

__all__ = ["ffn_gateup_kernel", "ffn_gateup"]


def ffn_gateup_kernel(x_ref, wg_ref, wu_ref, o_ref, accg_ref, accu_ref, *, activation):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...]
    accg_ref[...] += jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    accu_ref[...] += jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        gated = _ACTIVATIONS[activation](accg_ref[...]) * accu_ref[...]
        o_ref[...] = gated.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_m", "block_n", "block_k", "interpret"),
)
def ffn_gateup(
    x: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    *,
    activation: str = "silu",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """``act(x @ w_gate) * (x @ w_up)`` with fused gating.  2-D, block-divisible."""
    m, k = x.shape
    kg, f = w_gate.shape
    assert w_up.shape == (kg, f) and kg == k
    assert m % block_m == 0 and f % block_n == 0 and k % block_k == 0
    grid = (m // block_m, f // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(ffn_gateup_kernel, activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, f), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, block_n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, w_gate, w_up)
