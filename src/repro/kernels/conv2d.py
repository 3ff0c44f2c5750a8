"""Tiled implicit-GEMM conv2d (Pallas TPU) with the fused epilogue program.

The paper's three demo apps are convolution-dominated, and until this kernel
every ``conv2d`` node lowered through dense ``lax.conv_general_dilated`` --
outside the Pallas/tuning/epilogue system the matmul family already rides.
This kernel closes that gap: the convolution is executed as a GEMM whose
``A`` operand (the im2col patch matrix) is **materialized tile-by-tile in
VMEM and never in HBM**.

GEMM view (per batch image)::

    M = OH x OW   (output pixels)       N = O  (output channels)
    K = C x kh x kw                     acc[M, N] += patch[M, K] @ W[K, N]

Tiling: grid ``(N_batch, OH/block_h, O/block_o)`` and -- with ``block_c``
set -- a fourth tiled-K axis ``C/block_c``.  Each grid step owns a
``[block_h * OW, block_o]`` output tile.  The input image arrives as an NHWC
VMEM block per batch element (the wrapper transposes + zero-pads once in
HBM -- that is *padding*, not im2col); the kernel then walks the ``kh x kw``
filter taps, slicing a ``[block_h, OW, block_c]`` patch per tap out of the
resident slab (strided rows/cols for ``stride > 1``), reshaping it to
``[block_h * OW, block_c]`` and feeding the MXU.

``block_c == 0`` keeps the legacy resident-image contraction: all of
``K = C * kh * kw`` inside one grid step, no accumulator scratch.  With
``block_c > 0`` the contraction is *tiled over K*: the innermost grid axis
walks channel blocks, a cross-step VMEM accumulator scratch (f32, or int32
for W8A8) carries partial sums, and bias/rescale/activation/epilogue run
once on the **last** K step -- exactly ``dense_matmul``'s (i, j, k) grid
shape, with the per-step K slab being ``block_k = block_c * kh * kw`` of the
GEMM's K.  VMEM pressure then scales with ``block_c``, not ``C``, so
wide-channel layers stop tripping the ``lax.conv`` VMEM fallback; the
Pallas TPU grid pipeline streams the next step's image/filter blocks
HBM->VMEM while the current step computes (automatic double-buffering --
the explicit hand-rolled variant lives in ``dense_matmul``'s /
``quant_matmul``'s ``pipeline=2`` path).

Three schemes share the kernel body, selected by operand dtypes:

* **dense f32** -- f32 patches x f32 filters, f32 accumulation (``ws=None``).
* **channel-pruned** -- identical body; the ``ops.conv2d`` wrapper gathers
  the surviving input channels (channelcompact/colcompact masks) *before*
  the layout transform, so K shrinks by the pruned ratio and the kernel
  contracts only live channels.
* **INT8** -- int8 filters.  With int8 patches (W8A8: activations quantized
  by the calibrated static scale) the MXU contracts int8 x int8 into an
  **int32** accumulator; with f32 patches (W8-only) the filter tile is
  dequantized in VMEM (cast; per-output-channel scales deferred to ``ws``
  since ``x (*) (q * s[o]) == (x (*) q) * s[o]``).  ``ws`` carries the
  combined per-output-channel rescale (``w_scale`` or
  ``x_scale * w_scale``), applied once on the f32 accumulator.

Bias, the fused ``activation`` string, and the epilogue step *program*
(``("activation", fn)`` / ``("add"|"mul", slot)`` over per-tile side
operands, :func:`~.dense_matmul.apply_epilogue_steps`) all run on the f32
accumulator before the tile is written back -- the ``fuse_epilogue`` pass's
conv half, replacing the old post-``lax.conv`` jnp tail.

Use :func:`repro.kernels.ops.conv2d` for the public NCHW/OIHW API (layout,
padding, scheme selection, tuning-cache block resolution, and the
``lax.conv`` fallback matrix for unsupported configs).
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
    "VMEM_LIMIT_BYTES",
    "conv2d_gemm_kernel",
    "conv2d_gemm",
    "conv_out_hw",
    "conv_pad_hw",
    "conv_padding_token",
    "conv_vmem_workspace",
]


#: scoped-VMEM budget the conv kernel compiles with (``vmem_limit_bytes``)
VMEM_LIMIT_BYTES = 96 * 2**20


def _explicit_pads(padding) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """Normalize lax-style explicit padding ``((ph_lo, ph_hi), (pw_lo, pw_hi))``."""
    (a, b), (c, d) = padding
    return (int(a), int(b)), (int(c), int(d))


def conv_out_hw(h: int, w: int, kh: int, kw: int, stride: int, padding) -> Tuple[int, int]:
    """Output spatial dims of a stride-``stride`` conv: ``"SAME"``,
    ``"VALID"``, or lax-style explicit pad pairs."""
    if isinstance(padding, str):
        if padding == "SAME":
            return -(-h // stride), -(-w // stride)
        if padding == "VALID":
            return (h - kh) // stride + 1, (w - kw) // stride + 1
        raise ValueError(f"unsupported padding {padding!r} (SAME, VALID, or pad pairs)")
    (a, b), (c, d) = _explicit_pads(padding)
    return (h + a + b - kh) // stride + 1, (w + c + d - kw) // stride + 1


def conv_pad_hw(h: int, w: int, kh: int, kw: int, stride: int, padding) -> Tuple[int, int]:
    """(top, left) zero padding the implicit-GEMM input carries (XLA SAME
    semantics: total pad split low-heavy; explicit pairs pass through)."""
    if not isinstance(padding, str):
        (a, _), (c, _) = _explicit_pads(padding)
        return a, c
    if padding == "VALID":
        return 0, 0
    oh, ow = conv_out_hw(h, w, kh, kw, stride, padding)
    ph = max((oh - 1) * stride + kh - h, 0)
    pw = max((ow - 1) * stride + kw - w, 0)
    return ph // 2, pw // 2


def conv_padding_token(padding) -> str:
    """Tuning-key suffix distinguishing padding geometries (SAME -- the
    canonical case -- stays suffix-free; VALID and explicit pairs alias
    neither it nor each other)."""
    if isinstance(padding, str):
        return "" if padding == "SAME" else f"+{padding.lower()}"
    (a, b), (c, d) = _explicit_pads(padding)
    return f"+p{a}.{b}.{c}.{d}"


def _vmem_tile(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a ``[rows, cols]`` array in Mosaic's VMEM layout: the minor
    dim pads to 128 lanes, the second-minor to the dtype's sublane tiling
    (8 rows of 32 bits, so 32 rows of int8)."""
    sub = 8 * 4 // itemsize
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * itemsize


def conv_vmem_workspace(
    c: int,
    h: int,
    w: int,
    kh: int,
    kw: int,
    stride: int,
    padding: str,
    block_h: int,
    block_o: int,
    block_c: int = 0,
    x_itemsize: int = 4,
    w_itemsize: int = 4,
    n_sides: int = 0,
) -> dict:
    """Per-grid-step VMEM working set of the implicit-GEMM kernel in
    Mosaic's tiled layout: the image slab and filter tile (double-buffered
    by the grid pipeline), the output and epilogue-side tiles (likewise),
    the in-flight im2col patch tile, and the f32 accumulator.  ``block_c ==
    0`` means the resident-image path (all ``C`` channels in VMEM at once);
    ``block_c > 0`` is the tiled-K contraction, where only a
    ``block_c``-channel slab is resident per grid step (plus the cross-step
    accumulator scratch).  Shared by the ``ops.conv2d`` fallback guard,
    which admits a configuration only when ``total`` fits
    :data:`VMEM_LIMIT_BYTES`, and :meth:`ExecutionPlan.memory_estimate`
    (the im2col scratch never touches HBM, so it is VMEM-side working
    memory, not activation bytes)."""
    oh, ow = conv_out_hw(h, w, kh, kw, stride, padding)
    ohp = -(-max(oh, 1) // block_h) * block_h
    hp = (ohp - 1) * stride + kh
    wp = (max(ow, 1) - 1) * stride + kw
    bm = block_h * max(ow, 1)
    c_eff = min(c, block_c) if block_c else c
    image = 2 * hp * _vmem_tile(wp, c_eff, x_itemsize)
    weights = 2 * kh * kw * _vmem_tile(c_eff, block_o, w_itemsize)
    # one tap's strided load and its [bm, C] GEMM view
    patch = 2 * block_h * _vmem_tile(max(ow, 1), c_eff, x_itemsize)
    acc = (2 if block_c else 1) * _vmem_tile(bm, block_o, 4)
    out = 2 * (1 + n_sides) * _vmem_tile(bm, block_o, 4)
    return {
        "image": int(image),
        "weights": int(weights),
        "im2col_patch": int(patch),
        "acc": int(acc),
        "out": int(out),
        "total": int(image + weights + patch + acc + out),
    }


def conv2d_gemm_kernel(
    x_ref,  # [1, Hp, Wp, C or block_c] image slab (f32, or int8 for W8A8)
    w_ref,  # [kh*kw, C or block_c, block_o] filter taps (f32 or int8)
    ws_ref,  # [1, block_o] combined per-output-channel rescale, or None (f32)
    b_ref,  # [1, block_o] bias tile, or None
    side_refs,  # per-tile epilogue side operands, each [block_h*OW, block_o]
    o_ref,  # [block_h*OW, block_o] output tile
    acc_ref=None,  # cross-step VMEM accumulator (tiled-K only): f32 or int32
    *,
    stride: int,
    kh: int,
    kw: int,
    block_h: int,
    out_w: int,
    activation: Optional[str],
    epilogue: Tuple[Tuple, ...] = (),
):
    """One grid step of the implicit GEMM.

    ``acc_ref is None`` (legacy resident path): an (n, i, j) step contracts
    all ``C*kh*kw`` of K for one output tile, materializing one im2col patch
    tile per filter tap in VMEM.  With ``acc_ref`` (tiled-K path) this is an
    (n, i, j, kc) step: it contracts one ``block_c``-channel slab of K into
    the cross-step accumulator -- zeroed at ``kc == 0``, finished (rescale /
    bias / activation / epilogue + output write) at the last ``kc``."""
    i = pl.program_id(1)
    c = x_ref.shape[3]
    bm = block_h * out_w
    a8 = jnp.issubdtype(x_ref.dtype, jnp.integer)
    acc = jnp.zeros((bm, o_ref.shape[1]), jnp.int32 if a8 else jnp.float32)
    for ki in range(kh):
        for kj in range(kw):
            # strided ref loads: Mosaic has no strided slice of a loaded value
            rows = x_ref[
                0,
                pl.ds(i * (block_h * stride) + ki, block_h, stride=stride),
                pl.ds(kj, out_w, stride=stride),
                :,
            ]
            patch = rows.reshape(bm, c)  # the im2col tile -- VMEM only
            wk = w_ref[ki * kw + kj]  # [C, block_o]
            if a8:
                # W8A8: int8 x int8 -> int32 on the MXU, exact accumulation
                acc += jnp.dot(patch, wk, preferred_element_type=jnp.int32)
            else:
                # dense f32, or W8-only (int8 filter tile dequantized in
                # VMEM; per-channel scales deferred to ws)
                acc += jnp.dot(
                    patch.astype(jnp.float32),
                    wk.astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                )

    def _finish(a):
        a = a.astype(jnp.float32)
        if ws_ref is not None:
            a = a * ws_ref[...].astype(jnp.float32)
        if b_ref is not None:
            a = a + b_ref[...].astype(jnp.float32)
        a = _ACTIVATIONS[activation](a)
        a = apply_epilogue_steps(a, epilogue, side_refs)
        o_ref[...] = a.astype(o_ref.dtype)

    if acc_ref is None:
        _finish(acc)
        return
    kc = pl.program_id(3)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += acc

    @pl.when(kc == pl.num_programs(3) - 1)
    def _epilogue():
        _finish(acc_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "kh", "kw", "activation", "epilogue", "block_h", "block_o",
        "block_c", "interpret", "out_dtype",
    ),
)
def conv2d_gemm(
    x: jax.Array,
    w: jax.Array,
    ws: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    *sides: jax.Array,
    stride: int = 1,
    kh: int,
    kw: int,
    activation: Optional[str] = None,
    epilogue: Tuple[Tuple, ...] = (),
    block_h: int = 8,
    block_o: int = 128,
    block_c: int = 0,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Implicit-GEMM conv over pre-laid-out operands.

    ``x [N, Hp, Wp, C]`` NHWC, already zero-padded so that
    ``Hp == (OHp - 1) * stride + kh`` (``OHp`` a ``block_h`` multiple) and
    ``Wp == (OW - 1) * stride + kw``; ``w [kh*kw, C, Op]`` tap-major filters
    with ``Op`` a ``block_o`` multiple; ``ws``/``bias`` per-output-channel
    ``[Op]`` vectors; ``sides`` epilogue operands in the flattened output
    layout ``[N * OHp * OW, Op]``.  Returns ``[N * OHp * OW, Op]``.

    ``block_c == 0`` contracts all of K per grid step with the whole padded
    image VMEM-resident; ``block_c > 0`` (must divide ``C``) adds the tiled-K
    grid axis with the cross-step accumulator scratch -- the per-step K slab
    is ``block_k = block_c * kh * kw``.

    Use :func:`repro.kernels.ops.conv2d` for the NCHW/OIHW public API.
    """
    n, hp, wp, c = x.shape
    kk, c2, op = w.shape
    assert kk == kh * kw and c2 == c, (w.shape, (kh, kw, c))
    assert (hp - kh) % stride == 0, (hp, kh, stride)
    out_h = (hp - kh) // stride + 1
    out_w = (wp - kw) // stride + 1
    assert wp == (out_w - 1) * stride + kw, (wp, out_w, kw, stride)
    assert out_h % block_h == 0, (out_h, block_h)
    assert op % block_o == 0, (op, block_o)
    assert block_c >= 0 and (not block_c or c % block_c == 0), (c, block_c)
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    validate_epilogue(epilogue, len(sides))
    bm = block_h * out_w
    m = n * out_h * out_w
    for s in sides:
        assert s.shape == (m, op), (s.shape, (m, op))
    a8 = jnp.issubdtype(x.dtype, jnp.integer)
    out_dtype = out_dtype or (jnp.float32 if jnp.issubdtype(w.dtype, jnp.integer) else x.dtype)
    n_h_tiles = out_h // block_h
    tiled_k = bool(block_c)
    bc = block_c or c
    if tiled_k:
        grid = (n, n_h_tiles, op // block_o, c // block_c)
        in_specs = [
            pl.BlockSpec((1, hp, wp, bc), lambda nn, i, j, kc: (nn, 0, 0, kc)),
            pl.BlockSpec((kk, bc, block_o), lambda nn, i, j, kc: (0, kc, j)),
        ]
        vec_tile = pl.BlockSpec((1, block_o), lambda nn, i, j, kc: (0, j))
        out_tile = pl.BlockSpec(
            (bm, block_o), lambda nn, i, j, kc: (nn * n_h_tiles + i, j)
        )
        scratch = [pltpu.VMEM((bm, block_o), jnp.int32 if a8 else jnp.float32)]
        # kc is the contraction: it must stay sequential so the accumulator
        # scratch lives across it (the grid pipeline still double-buffers the
        # streamed image/filter blocks underneath)
        semantics = ("parallel", "parallel", "parallel", "arbitrary")
    else:
        grid = (n, n_h_tiles, op // block_o)
        in_specs = [
            pl.BlockSpec((1, hp, wp, c), lambda nn, i, j: (nn, 0, 0, 0)),
            pl.BlockSpec((kk, c, block_o), lambda nn, i, j: (0, 0, j)),
        ]
        vec_tile = pl.BlockSpec((1, block_o), lambda nn, i, j: (0, j))
        out_tile = pl.BlockSpec(
            (bm, block_o), lambda nn, i, j: (nn * n_h_tiles + i, j)
        )
        scratch = []
        semantics = ("parallel", "parallel", "parallel")
    args = [x, w]
    has_ws = ws is not None
    if has_ws:
        assert ws.shape == (op,), (ws.shape, op)
        in_specs.append(vec_tile)
        args.append(ws.reshape(1, op).astype(jnp.float32))
    has_bias = bias is not None
    if has_bias:
        assert bias.shape == (op,), (bias.shape, op)
        in_specs.append(vec_tile)
        args.append(bias.reshape(1, op))
    in_specs.extend([out_tile] * len(sides))
    args.extend(sides)
    n_sides = len(sides)

    def kern(*refs):
        # refs: x, w, [ws], [bias], *sides, o, [acc]
        pos = 2
        ws_ref = refs[pos] if has_ws else None
        pos += int(has_ws)
        b_ref = refs[pos] if has_bias else None
        pos += int(has_bias)
        conv2d_gemm_kernel(
            refs[0],
            refs[1],
            ws_ref,
            b_ref,
            refs[pos : pos + n_sides],
            refs[-1 - len(scratch)],
            refs[-1] if tiled_k else None,
            stride=stride,
            kh=kh,
            kw=kw,
            block_h=block_h,
            out_w=out_w,
            activation=activation,
            epilogue=epilogue,
        )

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_tile,
        out_shape=jax.ShapeDtypeStruct((m, op), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*args)
