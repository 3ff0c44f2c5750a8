"""Execution plans: op-registry compilation of LR graphs.

Replaces the monolithic if/elif interpreter that ``lowering.lower`` used to
be.  Compilation (:func:`compile_plan`) happens once per graph:

1. **handler resolution** -- every node op is looked up in the op registry
   (:func:`register_op`); unknown ops fail at *compile* time, not mid-run.
   Two handler sets exist: ``kernel`` (Pallas-backed GEMMs) and ``reference``
   (pure jnp, the XLA-native baseline).
2. **topological scheduling** -- Kahn's algorithm with graph order as the
   tiebreak, so plans execute correctly even if the node list was built out
   of order.
3. **buffer liveness** -- each step records which intermediates die after it
   (last use), and execution frees them immediately; peak-resident bytes can
   be estimated ahead of time via :meth:`ExecutionPlan.memory_estimate`
   (abstract eval, no FLOP spent).

The resulting :class:`ExecutionPlan` is callable as
``plan(params, *inputs)`` -- the exact contract of the old ``lower()`` --
and jits/grads/pjits like any JAX function.  Register new ops with::

    @register_op("my_op")
    def _my_op(p, xs, attrs, rt):
        return ...

Handlers take ``(params_dict, input_arrays, attrs, runtime)`` and return the
node's output array.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels import ops as kops
from ...kernels import ref as kref
from ...obs import metrics as _metrics
from ...obs import trace as _otrace
from ...robustness import faults as _faults
from ...robustness.breaker import GuardConfig, NumericGuardError
from .ir import Graph, Node

__all__ = [
    "BACKENDS",
    "EXEC_BACKENDS",
    "register_op",
    "registered_ops",
    "handlers_for",
    "guard_fallback_counts",
    "reset_guard_fallbacks",
    "jnp_route_counts",
    "Runtime",
    "Step",
    "ExecutionPlan",
    "BatchedPlan",
    "compile_plan",
]

_ACT = kref._ACT

#: ``kernel``: Pallas-backed GEMMs.  ``reference``: pure jnp (XLA baseline +
#: parity oracle).  ``quant``: the kernel set *overlaid* with the INT8
#: handlers -- the only backend that executes ``qlinear`` nodes with the
#: quantized Pallas kernels (selection mode for post-``quantize``-pass
#: plans); non-quantized ops fall through to their kernel handlers.
BACKENDS = ("kernel", "reference", "quant")

#: executable backends: the registration backends plus ``guarded`` -- a
#: policy backend (no handler table of its own) that tries a primary table
#: (``quant`` overlay by default) per step and demotes failures to the
#: ``reference`` handler under circuit breakers.  See ``_exec_guarded``.
EXEC_BACKENDS = BACKENDS + ("guarded",)

#: backend -> op -> handler(params, inputs, attrs, runtime) -> array
_HANDLERS: Dict[str, Dict[str, Callable]] = {b: {} for b in BACKENDS}


def handlers_for(backend: str) -> Dict[str, Callable]:
    """The effective handler table for ``backend`` (``quant`` inherits every
    kernel handler and overrides/extends with the quantized set; ``guarded``
    resolves to its default primary table -- the same overlay)."""
    if backend in ("quant", "guarded"):
        return {**_HANDLERS["kernel"], **_HANDLERS["quant"]}
    return dict(_HANDLERS[backend])


# --------------------------------------------------------------------------- #
# guarded-execution accounting (process-wide, mirrors conv_fallback_counts)    #
# --------------------------------------------------------------------------- #
#
# Process-wide demotion counts live in the metrics registry as the
# ``guard_demotions_total{op, scheme, reason}`` counter family (reason in
# {exception, numeric, breaker_open}); the per-plan breakdown lives in
# ``ExecutionPlan.guard_stats()``.  The accessors below are back-compat
# *views* over the registry.

_GUARD_METRIC = "guard_demotions_total"


def guard_fallback_counts() -> Dict[str, int]:
    """Process-wide guarded-executor demotion counts, keyed
    ``"op/scheme/reason"`` -- the guarded-backend sibling of
    :func:`repro.kernels.ops.conv_fallback_counts`.  A view over the
    ``guard_demotions_total`` registry family."""
    counts = _metrics.registry().label_counts(
        _GUARD_METRIC, "op", "scheme", "reason"
    )
    return {k: int(v) for k, v in counts.items()}


def reset_guard_fallbacks() -> None:
    _metrics.registry().reset(_GUARD_METRIC)


#: work a kernel-backend handler hands to jnp instead of a Pallas kernel,
#: by op and reason (counted at trace time under jit): a ``fused_elementwise``
#: node the tiled kernel cannot express, or a GEMM/conv epilogue that runs
#: as a jnp tail after the kernel -- the siblings of conv_fallback_total
_JNP_ROUTE_METRIC = "kernel_jnp_route_total"


def jnp_route_counts() -> Dict[str, int]:
    """Kernel-backend jnp routes keyed ``"op/reason"`` -- a view over the
    ``kernel_jnp_route_total`` registry family."""
    counts = _metrics.registry().label_counts(_JNP_ROUTE_METRIC, "op", "reason")
    return {k: int(v) for k, v in counts.items()}


def _count_jnp_route(op: str, reason: str) -> None:
    _metrics.registry().counter(_JNP_ROUTE_METRIC, op=op, reason=reason).inc()


def _node_scheme(n: Node) -> str:
    """The quantization scheme a node executes under -- the breaker-key
    dimension that separates an INT8 kernel family from its f32 sibling."""
    if n.op in ("qlinear", "qconv2d"):
        s = n.attrs.get("scheme")
        if s:
            return s
        return "w8a8" if n.attrs.get("x_scale") is not None else "w8"
    return "f32"


def _check_finite(y) -> None:
    """Post-step numeric guard: raise :class:`NumericGuardError` when any
    concrete inexact leaf of ``y`` contains NaN/Inf.  Tracers (jit/vmap
    tracing) are skipped -- the guard is an eager-mode contract."""
    for leaf in jax.tree.leaves(y):
        if isinstance(leaf, jax.core.Tracer):
            continue
        if jnp.issubdtype(jnp.result_type(leaf), jnp.inexact) and not bool(
            jnp.all(jnp.isfinite(leaf))
        ):
            raise NumericGuardError("non-finite values in step output")


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution-time knobs threaded to every handler."""

    backend: str
    interpret: Optional[bool] = None


def register_op(op: str, backends: Sequence[str] = BACKENDS):
    """Decorator: register an op handler for one or more backends."""

    def deco(fn: Callable) -> Callable:
        for b in backends:
            if b not in _HANDLERS:
                raise ValueError(f"unknown backend {b!r}")
            _HANDLERS[b][op] = fn
        return fn

    return deco


def registered_ops(backend: str = "kernel") -> List[str]:
    return sorted(handlers_for(backend))


# --------------------------------------------------------------------------- #
# epilogue programs (attached by the fuse_epilogue pass)                       #
# --------------------------------------------------------------------------- #
#
# A GEMM/conv node may carry an ``epilogue`` attr: a tuple of steps run on its
# output after bias + the fused ``activation`` attr.  Side-operand slots index
# the *node's own inputs* (like fused_elementwise steps index its inputs), and
# layer/instance-norm scale/bias live in the node's params under
# ``{pkey}_scale`` / ``{pkey}_bias``:
#
#   ("activation", fn) | ("add", j) | ("mul", j)
#   ("norm_layer", pkey, eps) | ("norm_instance", pkey, eps)


def _steps_local(steps, xs, p):
    """Resolve graph-form steps (side slots indexing the node's inputs, norm
    scale/bias under ``{pkey}_scale``/``{pkey}_bias`` params) into the
    kernel-local form shared with :func:`kref.apply_steps_ref` and the Pallas
    kernels: ``(steps, sides, norm_params)`` with renumbered slots."""
    out, sides, norms = [], [], []
    for step in steps:
        kind = step[0]
        if kind == "activation":
            out.append(step)
        elif kind in ("add", "mul"):
            sides.append(xs[step[1]])
            out.append((kind, len(sides) - 1))
        elif kind in ("norm_layer", "norm_instance"):
            pkey, eps = step[1], step[2]
            norms.append((p[f"{pkey}_scale"], p[f"{pkey}_bias"]))
            out.append(
                ("norm" if kind == "norm_layer" else kind, len(norms) - 1, eps)
            )
        elif kind == "norm_rms":  # decoder RMSNorm: scale-only, no bias param
            pkey, eps = step[1], step[2]
            norms.append((p[f"{pkey}_scale"], None))
            out.append((kind, len(norms) - 1, eps))
        elif kind == "rope":  # position ids stream in as a side operand
            sides.append(xs[step[1]])
            out.append((kind, len(sides) - 1, step[2], step[3]))
        else:
            raise NotImplementedError(f"step {kind}")
    return out, sides, norms


def _apply_epilogue(y, epilogue, xs, p):
    """jnp fallback applier -- delegates to the shared step interpreter
    (identical math to the unfused op handlers, so reference-backend plans
    stay bit-exact with their unfused counterparts)."""
    if not epilogue:
        return y
    steps, sides, norms = _steps_local(epilogue, xs, p)
    return kref.apply_steps_ref(y, steps, sides, norms)


def _kernel_epilogue(epilogue, xs, out_shape, op):
    """Translate an epilogue into the Pallas matmul's kernel-local form:
    ``(steps, sides)`` with slots renumbered into ``sides``.  Returns
    ``(None, None)`` when the program cannot run tiled in-kernel (norm steps
    need whole rows; mismatched side shapes cannot be streamed per-tile) --
    callers then fall back to :func:`_apply_epilogue` after the GEMM, and
    the jnp tail is counted under ``op`` in ``kernel_jnp_route_total``."""
    steps, sides = [], []
    for step in epilogue:
        kind = step[0]
        if kind == "activation":
            steps.append(step)
        elif kind in ("add", "mul"):
            s = xs[step[1]]
            if tuple(s.shape) != tuple(out_shape):
                _count_jnp_route(op, "epilogue_broadcast_side")
                return None, None
            sides.append(s)
            steps.append((kind, len(sides) - 1))
        else:  # norm_layer / norm_instance: need full rows / spatial planes
            _count_jnp_route(op, f"epilogue_{kind}")
            return None, None
    return tuple(steps), tuple(sides)


# --------------------------------------------------------------------------- #
# handlers: GEMM family (kernel vs reference differ)                           #
# --------------------------------------------------------------------------- #


@register_op("linear", backends=("kernel",))
def _linear_kernel(p, xs, a, rt):
    epi = a.get("epilogue") or ()
    out_shape = (*xs[0].shape[:-1], p["w"].shape[1])
    steps, sides = _kernel_epilogue(epi, xs, out_shape, "linear")
    if steps is None:  # not tile-fusable: run the GEMM, apply epilogue in jnp
        y = kops.matmul(
            xs[0], p["w"], p.get("b"), activation=a.get("activation"),
            interpret=rt.interpret,
        )
        return _apply_epilogue(y, epi, xs, p)
    return kops.matmul(
        xs[0], p["w"], p.get("b"), activation=a.get("activation"),
        epilogue=steps, epilogue_sides=sides, interpret=rt.interpret,
    )


@register_op("linear", backends=("reference",))
def _linear_ref(p, xs, a, rt):
    y = kref.matmul_ref(xs[0], p["w"], p.get("b"), activation=a.get("activation"))
    return _apply_epilogue(y, a.get("epilogue") or (), xs, p)


@register_op("sparse_linear", backends=("kernel",))
def _sparse_linear_kernel(p, xs, a, rt):
    fmt = a["format"]
    epi = a.get("epilogue") or ()
    if fmt in ("colcompact", "channelcompact"):
        values = p["values"]
        out_shape = (*xs[0].shape[:-1], values.shape[1])
        steps, sides = _kernel_epilogue(epi, xs, out_shape, "sparse_linear")
        kw = dict(activation=a.get("activation"), interpret=rt.interpret)
        if steps is not None:
            kw.update(epilogue=steps, epilogue_sides=sides)
        if fmt == "colcompact":
            y = kops.col_matmul(xs[0], values, p["kept"], p.get("b"), **kw)
        else:
            y = kops.matmul(xs[0], values, p.get("b"), **kw)
        return y if steps is not None else _apply_epilogue(y, epi, xs, p)
    if fmt == "pbcsr":
        # band-dispatched kernel: tile-fusable epilogues run on the f32
        # accumulator inside each band's kernel (sides sliced per band);
        # norm steps / broadcast sides fall back to the jnp tail
        nb, _, _, bn = p["values"].shape
        out_shape = (*xs[0].shape[:-1], nb * bn)
        steps, sides = _kernel_epilogue(epi, xs, out_shape, "sparse_linear")
        kw = dict(
            activation=a.get("activation"), bands=a.get("bands"),
            interpret=rt.interpret,
        )
        if steps is not None:
            kw.update(epilogue=steps, epilogue_sides=sides)
        y = kops.bsr_matmul(xs[0], p["values"], p["block_rows"], p.get("b"), **kw)
        return y if steps is not None else _apply_epilogue(y, epi, xs, p)
    raise NotImplementedError(f"sparse format {fmt}")


@register_op("sparse_linear", backends=("reference",))
def _sparse_linear_ref(p, xs, a, rt):
    fmt = a["format"]
    if fmt == "colcompact":
        y = kref.matmul_ref(
            jnp.take(xs[0], p["kept"], axis=-1), p["values"], p.get("b"),
            activation=a.get("activation"),
        )
    elif fmt == "channelcompact":
        y = kref.matmul_ref(
            xs[0], p["values"], p.get("b"), activation=a.get("activation")
        )
    elif fmt == "pbcsr":
        x = xs[0]
        y = kref.bsr_matmul_ref(
            x.reshape(-1, x.shape[-1]), p["values"], p["block_rows"], p.get("b"),
            activation=a.get("activation"),
        ).reshape(*x.shape[:-1], -1)
    else:
        raise NotImplementedError(f"sparse format {fmt}")
    return _apply_epilogue(y, a.get("epilogue") or (), xs, p)


# --------------------------------------------------------------------------- #
# handlers: quantized GEMM family (produced by the ``quantize`` pass)          #
# --------------------------------------------------------------------------- #
#
# ``qlinear`` node contract -- params: ``values`` int8 [K', N] (+ ``kept``
# for colcompact, ``b`` f32), ``w_scale`` f32 [N]; attrs: ``format`` in
# {dense, colcompact, channelcompact}, ``scheme`` in {w8, w8a8} (+
# ``x_scale`` float when w8a8), plus the usual activation/epilogue attrs and
# a ``bytes_saved`` annotation from the pass.


@register_op("qlinear", backends=("quant",))
def _qlinear_quant(p, xs, a, rt):
    """INT8 Pallas path: W8A8 (int32 MXU accumulation) when the node carries
    a calibrated activation scale, else W8-only (per-tile VMEM dequant)."""
    x = xs[0]
    if a.get("format") == "colcompact":
        x = jnp.take(x, p["kept"], axis=-1)
    epi = a.get("epilogue") or ()
    out_shape = (*xs[0].shape[:-1], p["values"].shape[1])
    steps, sides = _kernel_epilogue(epi, xs, out_shape, "qlinear")
    kw = dict(
        x_scale=a.get("x_scale"), activation=a.get("activation"),
        interpret=rt.interpret, _format=a.get("format", "dense"),
    )
    if steps is not None:
        kw.update(epilogue=steps, epilogue_sides=sides)
    y = kops.qmatmul(x, p["values"], p["w_scale"], p.get("b"), **kw)
    return y if steps is not None else _apply_epilogue(y, epi, xs, p)


@register_op("qlinear", backends=("reference",))
def _qlinear_ref(p, xs, a, rt):
    """jnp oracle: dequantized weights (and fake-quantized activations for
    w8a8) through the f32 reference GEMM -- simulates the kernel's integer
    math bit-closely, and gives memory_estimate an abstract-evalable body."""
    x = xs[0]
    if a.get("format") == "colcompact":
        x = jnp.take(x, p["kept"], axis=-1)
    y = kref.qmatmul_ref(
        x, p["values"], p["w_scale"], p.get("b"),
        x_scale=a.get("x_scale"), activation=a.get("activation"),
    )
    return _apply_epilogue(y, a.get("epilogue") or (), xs, p)


def _conv_call_kwargs(p, a, rt):
    """Shared kwarg plumbing for the conv kernel handlers."""
    return dict(
        stride=a.get("stride", 1), padding=a.get("padding", "SAME"),
        groups=a.get("groups", 1), dilation=a.get("dilation", 1),
        kept=p.get("kept"), activation=a.get("activation"),
        interpret=rt.interpret, _format=a.get("format", "dense"),
    )


def _conv_out_shape(p, xs, a, wkey="w"):
    x, w = xs[0], p[wkey]
    oh, ow = kops.conv_out_hw(
        x.shape[2], x.shape[3], w.shape[2], w.shape[3],
        a.get("stride", 1), a.get("padding", "SAME"),
    )
    return (x.shape[0], w.shape[0], oh, ow)


@register_op("conv2d", backends=("kernel",))
def _conv2d_kernel(p, xs, a, rt):
    """Pallas implicit-GEMM path: tile-fusable epilogue steps (activation /
    add / mul with output-shaped sides) run on the f32 accumulator inside
    the kernel; norm steps and broadcast sides keep the jnp tail.  Channel-
    pruned convs (``format="channelcompact"``, ``kept`` param) contract only
    the surviving input channels.  Unsupported configs (groups, dilation,
    VMEM overflow) auto-fall back to lax.conv inside the wrapper."""
    epi = a.get("epilogue") or ()
    steps, sides = _kernel_epilogue(epi, xs, _conv_out_shape(p, xs, a), "conv2d")
    kw = _conv_call_kwargs(p, a, rt)
    if steps is not None:
        kw.update(epilogue=steps, epilogue_sides=sides)
    y = kops.conv2d(xs[0], p["w"], p.get("b"), **kw)
    return y if steps is not None else _apply_epilogue(y, epi, xs, p)


@register_op("conv2d", backends=("reference",))
def _conv2d_ref(p, xs, a, rt):
    """jnp oracle: lax.conv at f32 accumulation (+ the channel gather for
    pruned convs), epilogue as a jnp tail."""
    x = xs[0]
    if p.get("kept") is not None:
        x = jnp.take(x, p["kept"], axis=1)
    y = kref.conv2d_ref(
        x, p["w"], p.get("b"), stride=a.get("stride", 1),
        padding=a.get("padding", "SAME"), groups=a.get("groups", 1),
        dilation=a.get("dilation", 1), activation=a.get("activation"),
    )
    return _apply_epilogue(y, a.get("epilogue") or (), xs, p)


@register_op("qconv2d", backends=("quant",))
def _qconv2d_quant(p, xs, a, rt):
    """INT8 Pallas conv: W8A8 (int8 patches x int8 filters -> int32 MXU
    accumulation) when the node carries a calibrated activation scale, else
    W8-only (filter tiles dequantized in VMEM) -- replacing the old
    dequant-to-f32-then-lax.conv path, so the f32 weight copy never
    materializes in HBM."""
    epi = a.get("epilogue") or ()
    steps, sides = _kernel_epilogue(
        epi, xs, _conv_out_shape(p, xs, a, "values"), "qconv2d"
    )
    kw = _conv_call_kwargs(p, a, rt)
    kw.update(w_scale=p["w_scale"], x_scale=a.get("x_scale"))
    if steps is not None:
        kw.update(epilogue=steps, epilogue_sides=sides)
    y = kops.conv2d(xs[0], p["values"], p.get("b"), **kw)
    return y if steps is not None else _apply_epilogue(y, epi, xs, p)


@register_op("qconv2d", backends=("reference",))
def _qconv2d_ref(p, xs, a, rt):
    """jnp oracle: dequantized filters (and fake-quantized activations for
    w8a8) through the f32 reference conv."""
    x = xs[0]
    if p.get("kept") is not None:
        x = jnp.take(x, p["kept"], axis=1)
    y = kref.qconv2d_ref(
        x, p["values"], p["w_scale"], p.get("b"), x_scale=a.get("x_scale"),
        stride=a.get("stride", 1), padding=a.get("padding", "SAME"),
        groups=a.get("groups", 1), dilation=a.get("dilation", 1),
        activation=a.get("activation"),
    )
    return _apply_epilogue(y, a.get("epilogue") or (), xs, p)


# --------------------------------------------------------------------------- #
# handlers: shared ops (same implementation on both backends)                  #
# --------------------------------------------------------------------------- #


@register_op("norm")
def _norm(p, xs, a, rt):
    kind = a["kind"]
    eps = a.get("eps", 1e-5)
    x = xs[0]
    if kind == "batch":  # inference: stored stats, per-channel (C of NCHW)
        s = p["scale"] / jnp.sqrt(p["var"] + eps)
        return (x - p["mean"][None, :, None, None]) * s[None, :, None, None] + p[
            "bias"
        ][None, :, None, None]
    if kind == "instance":  # per (N, C) over spatial
        # ``phases`` consecutive channels share statistics: the phases of
        # one full-resolution channel ahead of a pixel shuffle
        n, c, h, w = x.shape
        ph = a.get("phases", 1)
        xg = x.reshape(n, c // ph, ph, h, w)
        mu = xg.mean(axis=(2, 3, 4), keepdims=True)
        var = xg.var(axis=(2, 3, 4), keepdims=True)
        y = ((xg - mu) / jnp.sqrt(var + eps)).reshape(x.shape)
        return y * p["scale"][None, :, None, None] + p["bias"][None, :, None, None]
    if kind == "layer":  # over last dim
        mu = x.mean(axis=-1, keepdims=True)
        var = x.var(axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    raise NotImplementedError(kind)


@register_op("activation")
def _activation(p, xs, a, rt):
    return _ACT[a["fn"]](xs[0])


@register_op("add")
def _add(p, xs, a, rt):
    return xs[0] + xs[1]


@register_op("mul")
def _mul(p, xs, a, rt):
    return xs[0] * xs[1]


@register_op("fused_elementwise", backends=("reference",))
def _fused_elementwise(p, xs, a, rt):
    """jnp step interpreter: the parity oracle for the Pallas kernel (and
    the XLA-native baseline -- one HBM round-trip *per step*)."""
    steps, sides, norms = _steps_local(a["steps"], xs, p)
    return kref.apply_steps_ref(xs[0], steps, sides, norms)


def _ew_reference_reason(x, xs, steps, norms) -> Optional[str]:
    """Why the tiled kernel cannot express a fused_elementwise node, or
    None when it can."""
    if x.ndim < 2:
        return "rank"
    if any(s.shape != x.shape for s in xs[1:]):
        return "broadcast_side"
    for st in steps:  # the kernel's step vocabulary
        if st[0] not in ("activation", "add", "mul", "norm"):
            return st[0]  # norm_instance needs whole spatial planes
    if any(
        s is None or s.ndim != 1 or s.shape[-1] != x.shape[-1]
        for pair in norms for s in pair
    ):
        return "norm_params"
    return None


@register_op("fused_elementwise", backends=("kernel",))
def _fused_elementwise_kernel(p, xs, a, rt):
    """One VMEM-resident Pallas pass over the whole step program: one HBM
    read + write total.  Falls back to the jnp interpreter when the tiled
    kernel cannot express the node (rank < 2, broadcast sides, a step
    outside its vocabulary such as instance norm, non-vector norm params),
    counted per reason in ``kernel_jnp_route_total``."""
    x = xs[0]
    steps, sides, norms = _steps_local(a["steps"], xs, p)
    reason = _ew_reference_reason(x, xs, steps, norms)
    if reason is not None:
        _count_jnp_route("fused_elementwise", reason)
        return _fused_elementwise(p, xs, a, rt)
    return kops.fused_elementwise(x, sides, tuple(steps), norms, interpret=rt.interpret)


@register_op("concat")
def _concat(p, xs, a, rt):
    return jnp.concatenate(xs, axis=a.get("axis", 1))


@register_op("pixel_shuffle")
def _pixel_shuffle(p, xs, a, rt):
    x, r = xs[0], a["factor"]
    n, c, h, w = x.shape
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = x.transpose(0, 1, 4, 2, 5, 3)
    return x.reshape(n, c // (r * r), h * r, w * r)


@register_op("upsample")
def _upsample(p, xs, a, rt):
    r = a["factor"]
    return jnp.repeat(jnp.repeat(xs[0], r, axis=2), r, axis=3)


@register_op("pad_reflect")
def _pad_reflect(p, xs, a, rt):
    pd = a["pad"]
    return jnp.pad(xs[0], ((0, 0), (0, 0), (pd, pd), (pd, pd)), mode="reflect")


@register_op("gather_channels")
def _gather_channels(p, xs, a, rt):
    axis = a.get("axis", -1)
    idx = jnp.asarray(np.asarray(a["idx"]))
    x = xs[0]
    if a["mode"] == "gather":
        return jnp.take(x, idx, axis=axis)
    # scatter back to width n along axis
    if axis in (-1, x.ndim - 1):
        shp = x.shape[:-1] + (a["n"],)
        return jnp.zeros(shp, x.dtype).at[..., idx].set(x)
    if axis == 1:
        shp = (x.shape[0], a["n"]) + x.shape[2:]
        return jnp.zeros(shp, x.dtype).at[:, idx].set(x)
    raise NotImplementedError(axis)


@register_op("global_avg_pool")
def _global_avg_pool(p, xs, a, rt):
    return xs[0].mean(axis=(2, 3))


@register_op("broadcast_spatial")
def _broadcast_spatial(p, xs, a, rt):
    # fuse a [N, C] global feature into a [N, C, H, W] map
    return jnp.broadcast_to(
        xs[0][:, :, None, None],
        (xs[0].shape[0], xs[0].shape[1], xs[1].shape[2], xs[1].shape[3]),
    )


# --------------------------------------------------------------------------- #
# handlers: decoder-block ops (the transformer lowering)                       #
# --------------------------------------------------------------------------- #
#
# Node contracts (see models/transformer_graph.py, the builder):
#
#   embed      in (tokens [B, S] i32),              params {table [V, D]}
#   rmsnorm    in (x [..., D]),                     params {scale [D]}, attrs eps
#   rope       in (x [..., S, H*dh], pos [..., S]), attrs heads, theta
#   attention  phase="prefill": in (q, k, v [B, S, H|G * dh], lengths [B])
#              phase="decode":  in (q [B, 1, H*dh], k_new, v_new [B, 1, G*dh],
#                                   k_ctx, v_ctx [B, L, S, G, dh], lengths [B])
#              attrs n_heads, n_kv_heads (+ layer for decode)
#   ffn        in (x [..., D]),  params {w_gate, w_up [D, F]}, attrs activation
#   unembed    in (x [..., D]),  params {w [D, V_pad]}, attrs vocab
#
# ``lengths`` is the live token count per row: prefill masks each row to its
# own prompt (the batch is padded to a common S), decode masks the gathered
# page span and places the new token at slot == length (so the valid prefix
# stays contiguous -- exactly ``gqa_decode_step``'s slot = pos semantics).


def _attn_heads(q, k, v, a):
    """[B, S, H*dh] projections -> [B, H, S, dh] with KV groups repeated to
    the query head count (GQA: head gi*rep+ri reads group gi, matching the
    ``q.reshape(b, s, g, rep, dh)`` grouping in models/attention.py)."""
    h, g = a["n_heads"], a["n_kv_heads"]
    b, s, hd = q.shape
    dh = hd // h
    qh = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    kh = k.reshape(b, k.shape[1], g, dh).transpose(0, 2, 1, 3)
    vh = v.reshape(b, v.shape[1], g, dh).transpose(0, 2, 1, 3)
    if g != h:
        kh = jnp.repeat(kh, h // g, axis=1)
        vh = jnp.repeat(vh, h // g, axis=1)
    return qh, kh, vh, (b, s, hd)


def _attn_decode_merge(xs, a):
    """Merge the step's fresh k/v into the gathered cache span at
    slot == length, then head-split.  Returns (qh, kh, vh, shape, lengths+1)."""
    q, k_new, v_new, k_ctx, v_ctx, lengths = xs
    g = a["n_kv_heads"]
    dh = k_new.shape[-1] // g
    kc = k_ctx[:, a["layer"]]  # [B, S, G, dh]
    vc = v_ctx[:, a["layer"]]
    b, s_ctx = kc.shape[0], kc.shape[1]
    slot = (
        jnp.arange(s_ctx, dtype=jnp.int32)[None, :, None, None]
        == lengths[:, None, None, None]
    )
    k = jnp.where(slot, k_new.reshape(b, 1, g, dh), kc).reshape(b, s_ctx, -1)
    v = jnp.where(slot, v_new.reshape(b, 1, g, dh), vc).reshape(b, s_ctx, -1)
    qh, kh, vh, shape = _attn_heads(q, k, v, a)
    return qh, kh, vh, shape, lengths + 1


@register_op("attention", backends=("kernel",))
def _attention_kernel(p, xs, a, rt):
    """Flash-attention Pallas path.  Decode pads its single query row up to
    one (8-row) block; the valid-prefix mask keeps padded KV slots inert."""
    if a.get("phase") == "decode":
        qh, kh, vh, (b, s, hd), lens = _attn_decode_merge(xs, a)
        out = kops.attention(
            qh, kh, vh, lens, causal=False, block_q=8,
            interpret=rt.interpret,
        )
    else:
        q, k, v, lengths = xs
        qh, kh, vh, (b, s, hd) = _attn_heads(q, k, v, a)
        out = kops.attention(
            qh, kh, vh, lengths, causal=True, interpret=rt.interpret
        )
    return out.transpose(0, 2, 1, 3).reshape(b, s, hd)


@register_op("attention", backends=("reference",))
def _attention_ref(p, xs, a, rt):
    """jnp oracle (naive masked softmax at f32) -- also the abstract-eval
    body memory_estimate uses."""
    if a.get("phase") == "decode":
        qh, kh, vh, (b, s, hd), lens = _attn_decode_merge(xs, a)
        out = kref.flash_attention_ref(qh, kh, vh, lens, causal=False)
    else:
        q, k, v, lengths = xs
        qh, kh, vh, (b, s, hd) = _attn_heads(q, k, v, a)
        out = kref.flash_attention_ref(qh, kh, vh, lengths, causal=True)
    return out.transpose(0, 2, 1, 3).reshape(b, s, hd)


@register_op("embed")
def _embed(p, xs, a, rt):
    return jnp.take(p["table"], xs[0], axis=0)


@register_op("rmsnorm")
def _rmsnorm(p, xs, a, rt):
    # identical math to models/layers.rmsnorm: f32 compute, cast back
    # *before* the scale multiply
    x = xs[0]
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + a.get("eps", 1e-6))).astype(x.dtype) * p[
        "scale"
    ]


@register_op("rope")
def _rope(p, xs, a, rt):
    return kref.rope_ref(xs[0], xs[1], a["heads"], a.get("theta", 10000.0))


@register_op("ffn", backends=("kernel",))
def _ffn_kernel(p, xs, a, rt):
    return kops.ffn_gateup(
        xs[0], p["w_gate"], p["w_up"],
        activation=a.get("activation", "silu"), interpret=rt.interpret,
    )


@register_op("ffn", backends=("reference",))
def _ffn_ref(p, xs, a, rt):
    return kref.ffn_gateup_ref(
        xs[0], p["w_gate"], p["w_up"], activation=a.get("activation", "silu")
    )


@register_op("unembed")
def _unembed(p, xs, a, rt):
    # model-dtype matmul, pad-vocab classes masked: bit-identical to
    # transformer._unembed with w materialized as embed.table.T at build time
    logits = xs[0] @ p["w"]
    v, vp = a["vocab"], p["w"].shape[1]
    if v != vp:
        logits = jnp.where(
            jnp.arange(vp) < v, logits, jnp.asarray(-1e30, logits.dtype)
        )
    return logits


# --------------------------------------------------------------------------- #
# plan compilation                                                             #
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class Step:
    node: Node
    #: intermediate buffers whose last use is this step (freed right after)
    frees: Tuple[str, ...] = ()


def _topo_schedule(g: Graph) -> List[Node]:
    """Kahn's algorithm; original node order breaks ties (stable)."""
    defined = set(g.inputs)
    pending = list(g.nodes)
    order: List[Node] = []
    while pending:
        for i, n in enumerate(pending):
            if all(x in defined for x in n.inputs):
                order.append(n)
                defined.add(n.name)
                del pending[i]
                break
        else:
            names = [n.name for n in pending]
            raise ValueError(f"graph has a cycle or undefined inputs: {names}")
    return order


@dataclasses.dataclass(eq=False)
class ExecutionPlan:
    """A compiled, topologically scheduled program over registered op
    handlers.  Callable: ``plan(params, *inputs) -> outputs``."""

    graph: Graph
    steps: Tuple[Step, ...]
    backend: str
    interpret: Optional[bool] = None
    #: guarded-backend knobs; only meaningful (and auto-defaulted) when
    #: ``backend == "guarded"``
    guard: Optional[GuardConfig] = None

    def __post_init__(self):
        self._rt = Runtime(backend=self.backend, interpret=self.interpret)
        if self.backend == "guarded":
            if self.guard is None:
                self.guard = GuardConfig()
            self._handlers = handlers_for(self.guard.primary)
            self._ref_handlers = handlers_for("reference")
            self._guard_lock = threading.Lock()
            #: (op, scheme) -> CircuitBreaker, created lazily per step family
            self._breakers: Dict[Tuple[str, str], Any] = {}
            self.guard_counters: Dict[str, Any] = {
                "primary_ok": 0,
                "fallbacks": 0,
                "breaker_short_circuits": 0,
                "numeric_guard_trips": 0,
                "by_key": {},
            }
        else:
            if self.guard is not None:
                raise ValueError(
                    "guard config requires backend='guarded', "
                    f"got {self.backend!r}"
                )
            self._handlers = handlers_for(self.backend)

    # -- execution ----------------------------------------------------------- #
    def __call__(self, params: Dict[str, Dict[str, Any]], *args):
        return self.run_steps(params, *args)

    def run_steps(
        self,
        params: Dict[str, Dict[str, Any]],
        *args,
        observer: Optional[Callable[[str, Any], None]] = None,
    ):
        """Execute the plan; ``observer(name, value)`` (if given) sees every
        graph input and node output as it is produced -- the calibration hook
        used by :func:`repro.quant.calibrate.calibrate_plan`.

        Every step runs under ``jax.named_scope(<node name>)``.  Under
        tracing, a run on concrete arrays (a direct call, the guarded
        backend, :func:`repro.obs.profile_plan`) is one ``cat="plan"`` span
        holding one ``cat="step"`` span per step (op / scheme / backend /
        output shape; demotions annotated in-span: the ``demoted`` arg and a
        nested ``cat="guard"`` instant).  A run on tracers, inside
        :class:`BatchedPlan`'s ``jax.jit``, emits none."""
        if len(args) != len(self.graph.inputs):
            raise TypeError(
                f"plan expects {len(self.graph.inputs)} inputs "
                f"{self.graph.inputs}, got {len(args)}"
            )
        env: Dict[str, Any] = dict(zip(self.graph.inputs, args))
        if observer is not None:
            for name, v in env.items():
                observer(name, v)
        guarded = self.backend == "guarded"
        # spans time the run only when it runs: under jax.jit this body
        # runs once per compile, on tracers, and emits none
        traced = _otrace.enabled() and not any(
            isinstance(a, jax.core.Tracer) for a in args
        )
        run_span = _otrace.span(
            "plan", cat="plan", backend=self.backend, steps=len(self.steps),
            outputs=list(self.graph.outputs),
        ) if traced else _otrace.NULL_SPAN
        with run_span:
            for step in self.steps:
                n = step.node
                xs = [env[i] for i in n.inputs]
                p = params.get(n.name, {})
                sp = _otrace.span(
                    n.name, cat="step", op=n.op, scheme=_node_scheme(n),
                    backend=self.backend,
                ) if traced else _otrace.NULL_SPAN
                # the step's name rides in the op metadata of every
                # operation it lowers to (the device trace's ops too)
                with jax.named_scope(n.name), sp:
                    if guarded:
                        y = self._exec_guarded(n, p, xs, sp)
                    else:
                        y = self._handlers[n.op](p, xs, n.attrs, self._rt)
                    if traced:
                        sp.set("out_shape", list(jnp.shape(y)))
                env[n.name] = y
                if observer is not None:
                    observer(n.name, y)
                for f in step.frees:  # dead intermediate: release our reference
                    del env[f]
        outs = tuple(env[o] for o in self.graph.outputs)
        return outs[0] if len(outs) == 1 else outs

    # -- guarded execution ---------------------------------------------------- #
    def _exec_guarded(self, n: Node, p, xs, sp=_otrace.NULL_SPAN):
        """One step under the guarded contract: try the primary (kernel)
        handler behind the step family's circuit breaker and fault-injection
        hook; on any exception or a numeric-guard trip, record the failure
        and demote to the ``reference`` handler for this step only.  Shared
        ops (same function object on both backends) run unguarded -- there
        is nothing to demote to."""
        cfg = self.guard
        ref = self._ref_handlers.get(n.op)
        primary = self._handlers.get(n.op, ref)
        if ref is None or primary is ref:
            return primary(p, xs, n.attrs, self._rt)
        key = (n.op, _node_scheme(n))
        with self._guard_lock:
            br = self._breakers.get(key)
            if br is None:
                br = self._breakers[key] = cfg.make_breaker()
            allowed = br.allow()
        if not allowed:
            self._count_guard(key, "breaker_open", sp)
            return ref(p, xs, n.attrs, self._rt)
        fn = _faults.wrap_handler(n.op, primary)
        try:
            y = fn(p, xs, n.attrs, self._rt)
            if cfg.numeric_guards:
                _check_finite(y)
        except Exception as e:  # demote: any failure mode, never propagate
            with self._guard_lock:
                br.record_failure()
            self._count_guard(
                key,
                "numeric" if isinstance(e, NumericGuardError) else "exception",
                sp,
            )
            return ref(p, xs, n.attrs, self._rt)
        with self._guard_lock:
            br.record_success()
            self.guard_counters["primary_ok"] += 1
        return y

    def _count_guard(
        self, key: Tuple[str, str], reason: str, sp=_otrace.NULL_SPAN
    ) -> None:
        gkey = f"{key[0]}/{key[1]}/{reason}"
        with self._guard_lock:
            c = self.guard_counters
            c["fallbacks"] += 1
            if reason == "breaker_open":
                c["breaker_short_circuits"] += 1
            elif reason == "numeric":
                c["numeric_guard_trips"] += 1
            c["by_key"][gkey] = c["by_key"].get(gkey, 0) + 1
        _metrics.registry().counter(
            _GUARD_METRIC, op=key[0], scheme=key[1], reason=reason
        ).inc()
        if _otrace.enabled():
            sp.set("demoted", reason)  # annotate the enclosing step span
            _otrace.instant(
                f"demote:{key[0]}", cat="guard", scheme=key[1], reason=reason
            )

    def guard_stats(self) -> Dict[str, Any]:
        """Snapshot of this plan's guarded-execution state: demotion
        counters plus every breaker's state machine -- the payload
        ``AsyncPlanServer.health()`` surfaces per plan."""
        if self.backend != "guarded":
            return {}
        with self._guard_lock:
            c = self.guard_counters
            return {
                "counters": {
                    **{k: v for k, v in c.items() if k != "by_key"},
                    "by_key": dict(c["by_key"]),
                },
                "breakers": {
                    f"{op}/{scheme}": br.snapshot()
                    for (op, scheme), br in self._breakers.items()
                },
            }

    # -- introspection ------------------------------------------------------- #
    def memory_estimate(self, *inputs) -> Dict[str, Any]:
        """Peak-resident activation bytes under this schedule (abstract eval:
        no arrays are materialized).  ``inputs`` are arrays or
        ShapeDtypeStructs.  Params are counted as always-live."""
        structs = [
            x if isinstance(x, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))
            for x in inputs
        ]
        pstructs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
            self.graph.params,
        )
        nbytes = lambda s: int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize if s.shape else np.dtype(s.dtype).itemsize
        param_bytes = sum(nbytes(v) for v in jax.tree.leaves(pstructs))
        # per-dtype breakdown: quantized plans show their int8 payloads here
        # (the storage win the quantize pass bought)
        param_bytes_by_dtype: Dict[str, int] = {}
        for v in jax.tree.leaves(pstructs):
            key = np.dtype(v.dtype).name
            param_bytes_by_dtype[key] = param_bytes_by_dtype.get(key, 0) + nbytes(v)
        weight_bytes_saved = sum(
            int(n.attrs.get("bytes_saved", 0)) for n in self.graph.nodes
        )
        env: Dict[str, Any] = dict(zip(self.graph.inputs, structs))
        # prefer jnp reference handlers (abstract-eval anywhere), but fall
        # back to the plan's own backend for ops registered only there
        handlers = {**handlers_for(self.backend), **_HANDLERS["reference"]}
        rt = Runtime(backend="reference", interpret=self.interpret)
        peak = live = sum(nbytes(s) for s in env.values())
        per_step = []
        # conv steps do their im2col in VMEM, never in HBM: account that
        # scratch as per-step VMEM-side working memory, not activation bytes
        vmem_workspace_by_step: Dict[str, int] = {}
        for step in self.steps:
            n = step.node
            out = jax.eval_shape(
                lambda p, xs: handlers[n.op](p, xs, n.attrs, rt),
                pstructs.get(n.name, {}),
                [env[i] for i in n.inputs],
            )
            if n.op in ("conv2d", "qconv2d"):
                ws = self._conv_workspace(n, pstructs.get(n.name, {}), env[n.inputs[0]])
                if ws:
                    vmem_workspace_by_step[n.name] = ws
            env[n.name] = out
            live += nbytes(out)
            peak = max(peak, live)
            for f in step.frees:
                live -= nbytes(env.pop(f))
            per_step.append((n.name, nbytes(out), live))
        return {
            "peak_activation_bytes": int(peak),
            "param_bytes": int(param_bytes),
            "param_bytes_by_dtype": param_bytes_by_dtype,
            "weight_bytes_saved": int(weight_bytes_saved),
            "peak_total_bytes": int(peak + param_bytes),
            "per_step": per_step,
            "peak_vmem_workspace_bytes": max(vmem_workspace_by_step.values(), default=0),
            "vmem_workspace_by_step": vmem_workspace_by_step,
            "out_structs": tuple(env[o] for o in self.graph.outputs),
        }

    def _conv_workspace(self, n: Node, pstruct, x_struct) -> int:
        """Per-grid-step VMEM working set of one conv step through the
        implicit-GEMM kernel (resident image slab + filter tile + im2col
        patch + accumulator), at the tuned blocks when known, else the
        defaults."""
        wkey = "w" if n.op == "conv2d" else "values"
        if wkey not in pstruct or getattr(x_struct, "ndim", 0) != 4:
            return 0
        w = pstruct[wkey]
        a = n.attrs
        c = int(pstruct["kept"].shape[0]) if "kept" in pstruct else int(x_struct.shape[1])
        stride, padding = a.get("stride", 1), a.get("padding", "SAME")
        kh, kw = int(w.shape[2]), int(w.shape[3])
        nb, o = int(x_struct.shape[0]), int(w.shape[0])
        w8a8 = a.get("scheme") == "w8a8" or a.get("x_scale") is not None
        x_item = 1 if w8a8 else np.dtype(x_struct.dtype).itemsize
        w_item = np.dtype(w.dtype).itemsize
        interp = (
            kops.interpret_default() if self.interpret is None else self.interpret
        )
        # a 1x1 conv elects the direct-GEMM fast path at lowering time:
        # no im2col, no resident image -- it owns no conv-kernel workspace
        if kops.conv_gemm1x1_elected(kh, kw, a.get("groups", 1), padding, c):
            return 0
        # a step outside the kernel's matrix executes through lax.conv and
        # owns no Pallas VMEM workspace
        if kops.conv_fallback_reason(
            c, int(x_struct.shape[2]), int(x_struct.shape[3]), kh, kw, stride,
            padding, groups=a.get("groups", 1), dilation=a.get("dilation", 1),
            interpret=interp, x_itemsize=x_item, w_itemsize=w_item,
        ) is not None:
            return 0
        cache = kops.tuning_cache()
        fmt = f"{a.get('format', 'dense')}+" + (
            "f32" if n.op == "conv2d" else ("w8a8" if w8a8 else "w8")
        ) + kops.conv_padding_token(padding)
        # the executing handler appends the epilogue suffix only when the
        # program runs in-tile (norm steps / broadcast sides lower without
        # it), which this shape-only walk cannot decide -- probe both keys
        fmts = [fmt]
        epi = a.get("epilogue") or ()
        if epi:
            n_sides = sum(s[0] in ("add", "mul") for s in epi)
            fmts.insert(0, fmt + f"+e{len(epi)}s{n_sides}")
        shape = (nb, c, x_struct.shape[2], x_struct.shape[3], o, kh, kw, stride)
        dtype = jnp.int8 if w8a8 else x_struct.dtype
        blocks = next(
            (
                b for f in fmts
                if (b := cache.lookup_nd("conv2d", shape, dtype, f, interp))
            ),
            # no tuned winner: the wrapper would seed the shape-aware default
            # (resident when it fits VMEM, else the tiled-K granularity)
            kops._conv_default_blocks(
                c, int(x_struct.shape[2]), int(x_struct.shape[3]), kh, kw,
                stride, padding, x_item, w_item, interp,
            ),
        )
        return kops.conv_vmem_workspace(
            c, int(x_struct.shape[2]), int(x_struct.shape[3]), kh, kw, stride,
            padding, *blocks, x_itemsize=x_item, w_itemsize=w_item,
        )["total"]

    def summary(self) -> str:
        lines = [
            f"ExecutionPlan(backend={self.backend}, steps={len(self.steps)}, "
            f"inputs={self.graph.inputs}, outputs={self.graph.outputs})"
        ]
        for s in self.steps:
            fr = f"  frees {s.frees}" if s.frees else ""
            lines.append(f"  {s.node.name:24s} {s.node.op:18s} <- {s.node.inputs}{fr}")
        return "\n".join(lines)

    # -- batched serving ------------------------------------------------------ #
    def batched(self, batch_size: int, *, via_vmap: bool = False) -> "BatchedPlan":
        """Fixed-batch throughput wrapper: pads the caller's leading axis to a
        ``batch_size`` multiple, executes one jitted chunk call per slice
        (single compilation for every chunk), and slices the padding off.
        ``via_vmap=True`` vmaps the plan over the chunk axis instead of
        relying on the ops' native leading-batch polymorphism -- needed for
        graphs whose input shapes carry no batch dim of their own."""
        return BatchedPlan(self, batch_size, via_vmap=via_vmap)


@dataclasses.dataclass(eq=False)
class BatchedPlan:
    """Serve arbitrary-size macro-batches through a fixed-shape compiled
    plan.  Callable exactly like the plan: ``bp(params, *inputs)`` where every
    input's leading axis is the request batch.  The remainder chunk is padded
    (zeros) and the padding discarded, so the jitted chunk function compiles
    once per plan, never per request count."""

    plan: ExecutionPlan
    batch_size: int
    via_vmap: bool = False

    def __post_init__(self):
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        n_in = len(self.plan.graph.inputs)
        if self.plan.backend == "guarded":
            # guarded semantics (per-step try/except, breakers, numeric
            # guards) are eager-mode contracts -- tracing would bake one
            # arbitrary branch into the jitted chunk and blind the guards
            if self.via_vmap:
                raise ValueError(
                    "guarded plans execute eagerly; via_vmap needs tracing"
                )
            self._chunk = self.plan
        else:
            call = (
                jax.vmap(self.plan, in_axes=(None,) + (0,) * n_in)
                if self.via_vmap
                else self.plan
            )
            self._chunk = jax.jit(call)
        #: stats of the most recent __call__ (padding overhead is the serving
        #: cost of fixed-shape compilation; surfaced by PlanServer)
        self.last_stats: Dict[str, int] = {}
        #: cumulative over every chunk ever executed (all callers, all
        #: threads) -- the async scheduler reads this; guarded by _lock
        self.total_stats: Dict[str, int] = {
            "frames": 0, "batches": 0, "padded_frames": 0,
        }
        self._lock = threading.Lock()

    def _validate(self, inputs) -> int:
        if not inputs:
            raise TypeError("batched plan needs at least one input")
        b = inputs[0].shape[0]
        if b == 0:
            raise ValueError("empty macro-batch (leading axis has length 0)")
        for x in inputs[1:]:
            if x.shape[0] != b:
                raise ValueError(
                    f"inconsistent leading batch: {x.shape[0]} vs {b}"
                )
        return int(b)

    def run_chunk(self, params: Dict[str, Dict[str, Any]], *inputs):
        """Execute exactly ONE compiled chunk: the leading axis must be at
        most ``batch_size`` (a short chunk is zero-padded to the compiled
        shape and the padding sliced off the outputs).  This is the
        scheduler's entry point -- stats accumulate into ``total_stats``
        under a lock, so concurrent scheduler threads never corrupt them.
        Under tracing: ``chunk.pad``, ``chunk.call`` and ``chunk.slice``
        (``cat="plan"``) time the padding, the compiled call and the slice."""
        b = self._validate(inputs)
        bs = self.batch_size
        if b > bs:
            raise ValueError(
                f"run_chunk takes at most batch_size={bs} frames, got {b}"
            )
        xs = inputs
        if b < bs:
            short = bs - b
            with _otrace.span("chunk.pad", "plan"):
                xs = tuple(
                    jnp.concatenate(
                        [x, jnp.zeros((short,) + x.shape[1:], x.dtype)]
                    )
                    for x in xs
                )
        # returns once the chunk is enqueued, later when the runtime blocks
        with _otrace.span("chunk.call", "plan"):
            out = self._chunk(params, *xs)
        with self._lock:
            self.total_stats["frames"] += b
            self.total_stats["batches"] += 1
            self.total_stats["padded_frames"] += bs - b
        with _otrace.span("chunk.slice", "plan"):
            if isinstance(out, tuple):
                return tuple(o[:b] for o in out)
            return out[:b]

    def __call__(self, params: Dict[str, Dict[str, Any]], *inputs):
        b = self._validate(inputs)
        bs = self.batch_size
        chunks = [
            self.run_chunk(params, *(x[i : i + bs] for x in inputs))
            for i in range(0, b, bs)
        ]
        self.last_stats = {
            "frames": int(b),
            "batches": len(chunks),
            "padded_frames": int((-b) % bs),
        }
        if isinstance(chunks[0], tuple):
            return tuple(
                jnp.concatenate([c[j] for c in chunks])
                for j in range(len(chunks[0]))
            )
        return jnp.concatenate(chunks)


def compile_plan(
    g: Graph,
    *,
    backend: str = "kernel",
    interpret: Optional[bool] = None,
    guard: Optional[GuardConfig] = None,
) -> ExecutionPlan:
    """Compile ``g`` into an :class:`ExecutionPlan` (validates the graph,
    resolves handlers, schedules topologically, computes buffer liveness).
    ``backend="guarded"`` compiles a degradation-tolerant plan: each step
    tries ``guard.primary``'s handler and demotes failures to ``reference``
    (see :meth:`ExecutionPlan._exec_guarded`)."""
    if backend not in _HANDLERS and backend != "guarded":
        raise ValueError(f"unknown backend {backend!r}; have {EXEC_BACKENDS}")
    # schedule before validating: Graph.validate requires def-before-use node
    # order, which the Kahn schedule establishes for out-of-order builders
    order = _topo_schedule(g)
    g = dataclasses.replace(g, nodes=order)
    g.validate()
    handlers = handlers_for(backend)
    if backend == "guarded":  # an op with only a reference handler still runs
        handlers = {**handlers, **handlers_for("reference")}
    missing = sorted({n.op for n in order if n.op not in handlers})
    if missing:
        raise NotImplementedError(
            f"no {backend!r} handler for ops {missing}; "
            f"registered: {registered_ops(backend)}"
        )
    # liveness: an intermediate dies at its last consuming step.  Graph inputs
    # are caller-owned and graph outputs must survive, so neither is freed.
    keep = set(g.inputs) | set(g.outputs)
    last_use: Dict[str, int] = {}
    for i, n in enumerate(order):
        for x in n.inputs:
            last_use[x] = i
    steps = []
    for i, n in enumerate(order):
        frees = tuple(
            x for x, j in last_use.items() if j == i and x not in keep
        )
        steps.append(Step(node=n, frees=frees))
    return ExecutionPlan(
        graph=g, steps=tuple(steps), backend=backend, interpret=interpret,
        guard=guard,
    )
