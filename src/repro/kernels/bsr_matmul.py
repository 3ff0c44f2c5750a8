"""Block-sparse matmul over PBCSR weights (the paper's sparse execution engine,
TPU-native form -- DESIGN.md section 2).

``y[M, N] = x[M, K] @ W`` where W survives structured block pruning.  Weights
arrive *packed*: only surviving ``(bm, bn)`` blocks are stored
(``values[Nb, S, bm, bn]``), with one scalar-prefetched int32 block-row index
per block (``block_rows[Nb, S]``, -1 = padding).  Properties:

* pruned blocks are never read from HBM and never touch the MXU -- compute
  and memory scale with density, not with the dense shape;
* the index table lives in SMEM via ``PrefetchScalarGridSpec`` (scalar
  prefetch), so the x-tile address for step ``s`` is known before the DMA --
  no data-dependent stalls on the datapath (the paper's "irregular memory
  access" fix);
* the grid is output-stationary ``(M/bmx, Nb, S)`` with equal trip count S
  everywhere -- the load-balance contract established by the balanced
  projection or by the matrix-reorder bands (one call per band, exact S);
* padding blocks (index -1) clamp to x-block 0 and add zeros: exact, merely
  wasted work, which the reorder pass minimizes.

The bias+activation epilogue is fused exactly as in dense_matmul.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dense_matmul import _ACTIVATIONS, apply_epilogue_steps, validate_epilogue

__all__ = ["bsr_matmul_kernel", "bsr_matmul"]


def bsr_matmul_kernel(
    rows_ref,  # scalar-prefetch: [Nb, S] int32 block-row per step
    x_ref,  # [bmx, bm] tile of x (block-row selected via rows_ref)
    v_ref,  # [1, 1, bm, bn] packed weight block
    b_ref,  # [1, bn] bias tile or None
    side_refs,  # per-tile epilogue side operands, each [bmx, bn]
    o_ref,  # [bmx, bn] output tile
    acc_ref,  # VMEM f32 accumulator
    *,
    activation: Optional[str],
    epilogue: Tuple[Tuple, ...] = (),
):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j = pl.program_id(1)
    is_pad = rows_ref[j, s] < 0
    blk = jnp.dot(
        x_ref[...], v_ref[0, 0], preferred_element_type=jnp.float32
    )
    # padded steps contribute zero even if values were garbage (they are zero
    # by construction; the select also guards clamped x reads).
    acc_ref[...] += jnp.where(is_pad, 0.0, 1.0) * blk

    @pl.when(s == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...]
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.float32)
        acc = _ACTIVATIONS[activation](acc)
        # epilogue step program on the f32 accumulator (same vocabulary as
        # dense_matmul): sides stream per output tile, one per band slice
        acc = apply_epilogue_steps(acc, epilogue, side_refs)
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "activation", "epilogue", "block_m", "interpret", "out_dtype", "n_out",
    ),
)
def bsr_matmul(
    x: jax.Array,
    values: jax.Array,
    block_rows: jax.Array,
    bias: Optional[jax.Array] = None,
    *sides: jax.Array,
    n_out: Optional[int] = None,
    activation: Optional[str] = None,
    epilogue: Tuple[Tuple, ...] = (),
    block_m: int = 128,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Block-sparse ``epilogue(act(x @ W + bias))``.

    Args:
      x: ``[M, K]`` with M % block_m == 0, K % bm == 0.
      values: ``[Nb, S, bm, bn]`` packed surviving blocks (zeros at pads).
      block_rows: ``[Nb, S]`` int32 block-row index per packed block, -1 pad.
      bias: optional ``[Nb*bn]``.
      sides: ``[M, Nb*bn]`` epilogue side operands streamed per output tile.
      epilogue: step program (dense_matmul vocabulary) run on the f32
        accumulator at the last packed step -- the in-tile half of the
        ``fuse_epilogue`` pass for the PBCSR format.
      n_out: output width override (defaults to Nb*bn).
    """
    m, k = x.shape
    nb, s_steps, bm, bn = values.shape
    assert k % bm == 0, (k, bm)
    assert m % block_m == 0, (m, block_m)
    n = n_out or nb * bn
    assert n == nb * bn
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    validate_epilogue(epilogue, len(sides))
    for sv in sides:
        assert sv.shape == (m, n), (sv.shape, (m, n))
    out_dtype = out_dtype or x.dtype

    grid = (m // block_m, nb, s_steps)

    def x_index(i, j, s, rows):
        # pads (-1) clamp to x-block 0; their contribution is masked in-kernel
        return (i, jnp.maximum(rows[j, s], 0))

    out_tile = pl.BlockSpec((block_m, bn), lambda i, j, s, rows: (i, j))
    in_specs = [
        pl.BlockSpec((block_m, bm), x_index),
        pl.BlockSpec((1, 1, bm, bn), lambda i, j, s, rows: (j, s, 0, 0)),
    ]
    args = [x, values]
    has_bias = bias is not None
    if has_bias:
        assert bias.shape == (n,), bias.shape
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s, rows: (0, j)))
        args.append(bias.reshape(1, n))
    in_specs.extend([out_tile] * len(sides))
    args.extend(sides)
    n_sides = len(sides)

    def kern(*refs):
        # refs: rows, x, v, [bias], *sides, o, acc
        b_ref = refs[3] if has_bias else None
        first_side = 3 + int(has_bias)
        bsr_matmul_kernel(
            refs[0],
            refs[1],
            refs[2],
            b_ref,
            refs[first_side : first_side + n_sides],
            refs[-2],
            refs[-1],
            activation=activation,
            epilogue=epilogue,
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_tile,
        scratch_shapes=[pltpu.VMEM((block_m, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(block_rows, *args)
