"""Tiled INT8 matmul kernels (Pallas TPU) with the fused epilogue program.

Two schemes share one kernel body (selected by the activation dtype):

* **W8A8** -- ``x`` arrives int8 (statically-scaled activations, calibrated
  offline), weights are int8: the MXU contracts int8 x int8 into an **int32**
  VMEM accumulator, and a single f32 rescale at the last K step applies the
  combined ``x_scale * w_scale[n]`` per output column (folded into ``ws``
  before the call, so the kernel sees one rescale vector).  Both operands
  stream from HBM at a quarter of the f32 bytes.
* **W8-only** -- ``x`` stays f32 (no activation calibration needed), weights
  are int8: each weight tile is **dequantized in VMEM** (cast to f32 inside
  the kernel; per-column scales applied at the epilogue since
  ``x @ (q * s[n]) == (x @ q) * s[n]``), accumulating in f32.  Weight HBM
  traffic drops 4x -- the win for memory-bound GEMMs -- while activations
  keep full precision.  The pruned colcompact/channelcompact formats ride
  this scheme when no activation calibration is available (their values are
  plain ``[K', N]`` matrices); with a calibrated input range they run W8A8
  like any other qlinear -- the gather preserves values, so the input's
  scale applies to the gathered activations unchanged.

Bias, the fused ``activation`` string, and the epilogue step *program*
(``("activation", fn)`` / ``("add"|"mul", slot)`` over per-tile side
operands) all run on the rescaled f32 accumulator before the tile is written
back, exactly as in :mod:`.dense_matmul`.

Grid: ``(M/bm, N/bn, K/bk)``, K innermost so the accumulator lives across the
contraction.  The :func:`repro.kernels.ops.qmatmul` wrapper pads/rakes and
resolves block sizes through the tuning cache under the ``qmatmul`` key
family.  int8 min tile is (32, 128) -- every candidate block is a multiple.

``pipeline >= 2`` selects the hand-rolled double-buffered variant (grid
``(M/bm, N/bn)``, x/w left in HBM, K-slabs streamed through a ring of VMEM
scratch buffers with explicit async DMAs, the next slab's copy overlapping
the current contraction) -- see :mod:`.dense_matmul` for the lifecycle; here
the loop carry is int32 for W8A8 and the int8 weight slab still dequantizes
in VMEM for W8-only.  The int8 streams make this the kernel where manual
staging matters most: a depth-2 ring holds ``2 * bk * (bm + bn)`` int8
bytes, a quarter of the f32 footprint.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dense_matmul import _ACTIVATIONS, apply_epilogue_steps, validate_epilogue

__all__ = [
    "quant_matmul_kernel",
    "quant_matmul_pipelined_kernel",
    "quant_matmul",
]


def quant_matmul_kernel(
    x_ref,  # [bm, bk] int8 (W8A8) or f32 (W8-only)
    w_ref,  # [bk, bn] int8
    ws_ref,  # [1, bn] f32 combined rescale per output column
    b_ref,  # [1, bn] f32 bias tile or None
    side_refs,  # per-tile epilogue side operands, each [bm, bn]
    o_ref,  # [bm, bn] output tile
    acc_ref,  # VMEM accumulator: int32 (W8A8) or f32 (W8-only)
    *,
    activation: Optional[str],
    epilogue: Tuple[Tuple, ...] = (),
):
    """One (i, j, k) grid step; rescale + epilogue at the last k."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if jnp.issubdtype(x_ref.dtype, jnp.integer):
        # W8A8: int8 x int8 -> int32 on the MXU, exact integer accumulation
        acc_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.int32
        )
    else:
        # W8-only: dequantize the weight tile in VMEM (scale deferred to the
        # per-column rescale below), accumulate in f32
        acc_ref[...] += jnp.dot(
            x_ref[...], w_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32) * ws_ref[...].astype(jnp.float32)
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.float32)
        acc = _ACTIVATIONS[activation](acc)
        acc = apply_epilogue_steps(acc, epilogue, side_refs)
        o_ref[...] = acc.astype(o_ref.dtype)


def quant_matmul_pipelined_kernel(
    x_hbm,  # [M, K] int8 (W8A8) or f32 (W8-only), whole operand in HBM
    w_hbm,  # [K, N] int8, whole operand in HBM
    ws_ref,  # [1, bn] f32 combined rescale per output column
    b_ref,
    side_refs,
    o_ref,
    x_slots,  # VMEM [depth, bm, bk] ring of streamed x K-slabs
    w_slots,  # VMEM [depth, bk, bn] int8 ring of streamed w K-slabs
    sem,  # DMA semaphores [depth, 2] (slot x {x, w})
    *,
    block_k: int,
    n_steps: int,
    depth: int,
    activation: Optional[str],
    epilogue: Tuple[Tuple, ...] = (),
):
    """One (i, j) grid step of the hand-pipelined INT8 GEMM: K contracted by
    an in-kernel loop over slabs streamed through a ``depth``-deep ring, the
    DMA for slab ``s + depth - 1`` issued before slab ``s`` is awaited.  The
    accumulator is the loop carry (int32 for W8A8, f32 for W8-only); the
    per-column rescale + epilogue run once after the loop."""
    a8 = jnp.issubdtype(x_hbm.dtype, jnp.integer)

    bm, bn = o_ref.shape
    rows = pl.ds(pl.multiple_of(pl.program_id(0) * bm, bm), bm)
    cols = pl.ds(pl.multiple_of(pl.program_id(1) * bn, bn), bn)

    def copies(slot, step):
        ks = pl.ds(pl.multiple_of(step * block_k, block_k), block_k)
        return (
            pltpu.make_async_copy(
                x_hbm.at[rows, ks], x_slots.at[slot], sem.at[slot, 0]
            ),
            pltpu.make_async_copy(
                w_hbm.at[ks, cols], w_slots.at[slot], sem.at[slot, 1]
            ),
        )

    for p in range(min(depth - 1, n_steps)):  # warm-up: fill the ring
        for c in copies(p, p):
            c.start()

    def body(step, acc):
        ahead = step + depth - 1

        @pl.when(ahead < n_steps)
        def _prefetch():
            for c in copies(jax.lax.rem(ahead, depth), ahead):
                c.start()

        slot = jax.lax.rem(step, depth)
        for c in copies(slot, step):
            c.wait()
        if a8:
            return acc + jnp.dot(
                x_slots[slot], w_slots[slot], preferred_element_type=jnp.int32
            )
        return acc + jnp.dot(
            x_slots[slot], w_slots[slot].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(
        0, n_steps, body,
        jnp.zeros(o_ref.shape, jnp.int32 if a8 else jnp.float32),
    )
    acc = acc.astype(jnp.float32) * ws_ref[...].astype(jnp.float32)
    if b_ref is not None:
        acc = acc + b_ref[...].astype(jnp.float32)
    acc = _ACTIVATIONS[activation](acc)
    acc = apply_epilogue_steps(acc, epilogue, side_refs)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "activation", "epilogue", "block_m", "block_n", "block_k", "pipeline",
        "interpret", "out_dtype",
    ),
)
def quant_matmul(
    x: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    bias: Optional[jax.Array] = None,
    *sides: jax.Array,
    activation: Optional[str] = None,
    epilogue: Tuple[Tuple, ...] = (),
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    pipeline: int = 1,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """``epilogue(act((x @ w_q) * w_scale + bias))`` over 2-D block-aligned
    operands.  ``x`` int8 selects the W8A8 int32 path (``w_scale`` must
    already fold the activation scale in); f32 ``x`` selects the W8-only
    per-tile-dequantize path.  ``w_q [K, N]`` int8, ``w_scale [N]`` f32.
    ``pipeline >= 2`` selects the hand-rolled double-buffered K streaming
    path (that many VMEM slab slots in flight).

    Use :func:`repro.kernels.ops.qmatmul` for the padded/raked public API.
    """
    m, k = x.shape
    k2, n = w_q.shape
    assert k == k2, (x.shape, w_q.shape)
    assert w_q.dtype == jnp.int8, w_q.dtype
    assert w_scale.shape == (n,), (w_scale.shape, n)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        x.shape, w_q.shape, (block_m, block_n, block_k),
    )
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    validate_epilogue(epilogue, len(sides))
    for s in sides:
        assert s.shape == (m, n), (s.shape, (m, n))
    a8 = jnp.issubdtype(x.dtype, jnp.integer)
    pipelined = pipeline >= 2
    if pipelined:
        grid = (m // block_m, n // block_n)
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ]
        bias_tile = pl.BlockSpec((1, block_n), lambda i, j: (0, j))
        out_tile = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
        scratch = [
            pltpu.VMEM((pipeline, block_m, block_k), x.dtype),
            pltpu.VMEM((pipeline, block_k, block_n), w_q.dtype),
            pltpu.SemaphoreType.DMA((pipeline, 2)),
        ]
        semantics = ("parallel", "parallel")
    else:
        grid = (m // block_m, n // block_n, k // block_k)
        in_specs = [
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ]
        bias_tile = pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j))
        out_tile = pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j))
        scratch = [pltpu.VMEM((block_m, block_n), jnp.int32 if a8 else jnp.float32)]
        semantics = ("parallel", "parallel", "arbitrary")
    args = [x, w_q, w_scale.reshape(1, n).astype(jnp.float32)]
    has_bias = bias is not None
    if has_bias:
        assert bias.shape == (n,), bias.shape
        in_specs.append(bias_tile)
        args.append(bias.reshape(1, n))
    in_specs.extend([out_tile] * len(sides))
    args.extend(sides)
    n_sides = len(sides)

    def kern(*refs):
        # refs: x, w_q, ws, [bias], *sides, o, then scratch
        b_ref = refs[3] if has_bias else None
        first_side = 3 + int(has_bias)
        side_refs = refs[first_side : first_side + n_sides]
        if pipelined:
            quant_matmul_pipelined_kernel(
                refs[0],
                refs[1],
                refs[2],
                b_ref,
                side_refs,
                refs[-4],
                refs[-3],
                refs[-2],
                refs[-1],
                block_k=block_k,
                n_steps=k // block_k,
                depth=pipeline,
                activation=activation,
                epilogue=epilogue,
            )
        else:
            quant_matmul_kernel(
                refs[0],
                refs[1],
                refs[2],
                b_ref,
                side_refs,
                refs[-2],
                refs[-1],
                activation=activation,
                epilogue=epilogue,
            )

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_tile,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics
        ),
        interpret=interpret,
    )(*args)
