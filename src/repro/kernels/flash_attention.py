"""Flash attention (forward) as a Pallas TPU kernel.

The models' ``attention.sdpa(impl='chunked')`` is the jnp expression of this
algorithm (used for sharded lowering); this kernel is the TPU hot path: one
pass over KV blocks with the online-softmax (m, l, acc) recurrence held in
VMEM scratch -- no [Sq, Skv] score matrix ever touches HBM.

Grid: ``(B*H, Sq/bq, Skv/bk)`` with the KV axis innermost ("arbitrary") so
scratch carries across it.  Causal masking happens in-kernel from block
coordinates; fully-masked KV blocks still execute (Pallas grids are dense) --
the standard cost of the simple schedule, ~2x over the triangle.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention"]

NEG_INF = -1e30


def flash_attention_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, causal, bq, bk, len_ref=None,
):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_idx = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # [bq, d]
    k = k_ref[0].astype(jnp.float32)  # [bk, d]
    v = v_ref[0].astype(jnp.float32)  # [bk, d]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [bq, bk]
    cols = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        rows = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s = jnp.where(cols <= rows, s, NEG_INF)
    if len_ref is not None:
        # valid-prefix mask: only KV slots < length attend (paged decode where
        # Skv is padded out to a page multiple past the live cache entries)
        s = jnp.where(cols < len_ref[pl.program_id(0)], s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _flash_attention_kernel_len(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, causal, bq, bk,
):
    flash_attention_kernel(
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
        scale=scale, causal=causal, bq=bq, bk=bk, len_ref=len_ref,
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret", "scale")
)
def flash_attention(
    q: jax.Array,  # [B, H, Sq, d]
    k: jax.Array,  # [B, H, Skv, d]
    v: jax.Array,  # [B, H, Skv, d]
    kv_lengths: Optional[jax.Array] = None,  # [B] int32 valid KV prefix per row
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, d = q.shape
    skv = k.shape[2]
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf = k.reshape(bh, skv, d)
    vf = v.reshape(bh, skv, d)
    grid = (bh, sq // block_q, skv // block_k)
    q_spec = pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0))
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, d), jnp.float32),
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )
    if kv_lengths is None:
        out = pl.pallas_call(
            functools.partial(
                flash_attention_kernel,
                scale=scale, causal=causal, bq=block_q, bk=block_k,
            ),
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            scratch_shapes=scratch,
            compiler_params=params,
            interpret=interpret,
        )(qf, kf, vf)
    else:
        # per-(batch, head) lengths are scalar-prefetched into SMEM: a
        # [bh] int32 vector indexed by the grid's first coordinate
        lens = jnp.repeat(jnp.asarray(kv_lengths, jnp.int32).reshape(b), h)
        q_spec = pl.BlockSpec((1, block_q, d), lambda g, i, j, _: (g, i, 0))
        kv_spec = pl.BlockSpec((1, block_k, d), lambda g, i, j, _: (g, j, 0))
        out = pl.pallas_call(
            functools.partial(
                _flash_attention_kernel_len,
                scale=scale, causal=causal, bq=block_q, bk=block_k,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[q_spec, kv_spec, kv_spec],
                out_specs=q_spec,
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            compiler_params=params,
            interpret=interpret,
        )(lens, qf, kf, vf)
    return out.reshape(b, h, sq, d)
