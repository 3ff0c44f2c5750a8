"""Tiled dense matmul with a fused epilogue *program* (Pallas TPU).

This is (a) the baseline against which the BSR kernel is compared and (b) the
execution engine for column-/channel-compacted weights (a strictly smaller
dense GEMM).  The fused epilogue is the TPU materialization of the paper's
DSL fusion passes: beyond the single ``activation`` string (Conv/Linear +
BatchNorm + Activation in one kernel), the epilogue now accepts a step
*program* -- ``("activation", fn)`` / ``("add", slot)`` / ``("mul", slot)``
over per-tile side operands -- so bias + activation + residual-add + scale
all run on the f32 accumulator in registers before the tile is written back
(the ``fuse_epilogue`` pass's kernel half; no HBM round-trip for any
intermediate).

Grid: ``(M/bm, N/bn, K/bk)`` with a VMEM f32 accumulator; K innermost so the
accumulator lives across the contraction.  Block shapes default to MXU-square
128 and must divide the (padded) operand shapes -- the ops.py wrapper pads.

``pipeline >= 2`` switches to the hand-rolled double-buffered variant: the
grid drops to ``(M/bm, N/bn)``, the x/w operands stay in HBM
(``memory_space=ANY``), and the kernel itself streams ``[bm, bk]`` /
``[bk, bn]`` K-slabs into a ``pipeline``-deep ring of VMEM scratch buffers
with explicit async DMAs -- the copy for K-step ``k + depth - 1`` is started
*before* waiting on step ``k``'s, so HBM transfer of the next slab overlaps
the MXU contraction of the current one.  This is the explicit form of what
the Pallas grid pipeline does automatically for the ``pipeline == 1`` path;
it exists so the tuning cache can choose between compiler-scheduled and
hand-scheduled K streaming per shape (the 4th ``matmul``-family block field).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "dense_matmul_kernel",
    "dense_matmul_pipelined_kernel",
    "dense_matmul",
]


_ACTIVATIONS = {
    None: lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
}


def apply_epilogue_steps(acc, epilogue, side_refs):
    """Run an epilogue step program on the f32 accumulator tile -- the
    single in-kernel step interpreter shared by the dense, PBCSR, and INT8
    matmul kernels.  ``("add"|"mul", slot)`` streams ``side_refs[slot]``."""
    for step in epilogue:
        kind = step[0]
        if kind == "activation":
            acc = _ACTIVATIONS[step[1]](acc)
        elif kind in ("add", "mul"):
            s = side_refs[step[1]][...].astype(jnp.float32)
            acc = acc + s if kind == "add" else acc * s
        else:
            raise NotImplementedError(f"epilogue step {kind}")
    return acc


def validate_epilogue(epilogue, n_sides: int) -> None:
    """Wrapper-side validation shared by every epilogue-capable kernel."""
    for step in epilogue:
        if step[0] == "activation" and step[1] not in _ACTIVATIONS:
            raise ValueError(f"unknown epilogue activation {step[1]!r}")
        if step[0] in ("add", "mul") and not (0 <= step[1] < n_sides):
            raise ValueError(
                f"epilogue slot {step[1]} out of range ({n_sides} sides)"
            )


def dense_matmul_kernel(
    x_ref,
    w_ref,
    b_ref,
    side_refs,
    o_ref,
    acc_ref,
    *,
    activation: Optional[str],
    epilogue: Tuple[Tuple, ...] = (),
):
    """One (i, j, k) grid step: acc += x[i,k] @ w[k,j]; epilogue at last k.

    ``epilogue`` steps run on the f32 accumulator after bias + ``activation``;
    ``("add"|"mul", slot)`` streams side tile ``side_refs[slot]``.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...]
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.float32)
        acc = _ACTIVATIONS[activation](acc)
        acc = apply_epilogue_steps(acc, epilogue, side_refs)
        o_ref[...] = acc.astype(o_ref.dtype)


def dense_matmul_pipelined_kernel(
    x_hbm,  # [M, K] whole operand, left in HBM (memory_space=ANY)
    w_hbm,  # [K, N] whole operand, left in HBM (memory_space=ANY)
    b_ref,
    side_refs,
    o_ref,
    x_slots,  # VMEM [depth, bm, bk] ring of streamed x K-slabs
    w_slots,  # VMEM [depth, bk, bn] ring of streamed w K-slabs
    sem,  # DMA semaphores [depth, 2] (slot x {x, w})
    *,
    block_k: int,
    n_steps: int,
    depth: int,
    activation: Optional[str],
    epilogue: Tuple[Tuple, ...] = (),
):
    """One (i, j) grid step of the hand-pipelined GEMM: K is contracted by
    an in-kernel loop over ``n_steps`` slabs streamed HBM->VMEM through a
    ``depth``-deep double-buffer ring.  Slab ``s + depth - 1``'s DMA starts
    before slab ``s``'s is awaited, so the copy of the next operands overlaps
    the MXU work on the current ones; the accumulator is the loop carry."""

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
        return acc + jnp.dot(
            x_slots[slot], w_slots[slot], preferred_element_type=jnp.float32
        )

    acc = jax.lax.fori_loop(
        0, n_steps, body, jnp.zeros(o_ref.shape, jnp.float32)
    )
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
def dense_matmul(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *sides: jax.Array,
    activation: Optional[str] = None,
    epilogue: Tuple[Tuple, ...] = (),
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    pipeline: int = 1,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """``epilogue(act(x @ w + bias))`` -- 2-D operands, shapes multiples of
    the blocks; ``sides`` are [M, N] arrays streamed per-tile for the
    epilogue's add/mul slots.  ``pipeline >= 2`` selects the hand-rolled
    double-buffered K streaming path (that many VMEM slab slots in flight).

    Use :func:`repro.kernels.ops.matmul` for the padded/raked public API.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        x.shape,
        w.shape,
        (block_m, block_n, block_k),
    )
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    validate_epilogue(epilogue, len(sides))
    for s in sides:
        assert s.shape == (m, n), (s.shape, (m, n))
    out_dtype = out_dtype or x.dtype
    pipelined = pipeline >= 2
    if pipelined:
        grid = (m // block_m, n // block_n)
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        bias_tile = pl.BlockSpec((1, block_n), lambda i, j: (0, j))
        out_tile = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
        scratch = [
            pltpu.VMEM((pipeline, block_m, block_k), x.dtype),
            pltpu.VMEM((pipeline, block_k, block_n), w.dtype),
            pltpu.SemaphoreType.DMA((pipeline, 2)),
        ]
        semantics = ("parallel", "parallel")
    else:
        grid = (m // block_m, n // block_n, k // block_k)
        in_specs = [
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ]
        bias_tile = pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j))
        out_tile = pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j))
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]
        semantics = ("parallel", "parallel", "arbitrary")
    args = [x, w]
    has_bias = bias is not None
    if has_bias:
        assert bias.shape == (n,), bias.shape
        in_specs.append(bias_tile)
        args.append(bias.reshape(1, n))
    in_specs.extend([out_tile] * len(sides))
    args.extend(sides)
    n_sides = len(sides)

    def kern(*refs):
        # refs: x, w, [bias], *sides, o, then scratch
        b_ref = refs[2] if has_bias else None
        first_side = 2 + int(has_bias)
        side_refs = refs[first_side : first_side + n_sides]
        if pipelined:
            dense_matmul_pipelined_kernel(
                refs[0],
                refs[1],
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
            dense_matmul_kernel(
                refs[0],
                refs[1],
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
