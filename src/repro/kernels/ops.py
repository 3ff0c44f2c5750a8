"""Public jit'd wrappers around the Pallas kernels.

These handle the unglamorous parts -- leading-batch flattening, padding to
block multiples, interpret-mode selection (CPU container vs real TPU), band
dispatch for reordered BSR weights -- so models call one function per op.

Block sizes are no longer frozen at 128: when a call does not pin them
explicitly, they come from the :class:`TuningCache` -- keyed by
``(op, M, N, K, dtype, format)``, seeded with sane defaults (so tests never
pay a sweep), and able to sweep a small candidate grid once per shape when
tuning is enabled (``REPRO_TUNE=1`` or :func:`set_tuning`).  The cache
persists to JSON (``REPRO_TUNE_CACHE=path`` or ``save``/``load``) -- the
paper's compiler "parameter auto-tuning" applied to Pallas tiling.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics
from . import ref as _ref
from .bsr_matmul import bsr_matmul as _bsr_matmul
from .conv2d import VMEM_LIMIT_BYTES as _CONV_VMEM_LIMIT
from .conv2d import conv2d_gemm as _conv2d_gemm
from .conv2d import (
    conv_out_hw,
    conv_pad_hw,
    conv_padding_token,
    conv_vmem_workspace,
)
from .dense_matmul import dense_matmul as _dense_matmul
from .flash_attention import flash_attention as _flash_attention
from .fused_elementwise import fused_elementwise as _fused_elementwise
from .fused_ffn import ffn_gateup as _ffn_gateup
from .quant_matmul import quant_matmul as _quant_matmul

__all__ = [
    "interpret_default",
    "matmul",
    "bsr_matmul",
    "col_matmul",
    "conv2d",
    "conv_out_hw",
    "conv_padding_token",
    "conv_vmem_workspace",
    "conv_fallback_counts",
    "conv_fallback_reason",
    "reset_conv_fallbacks",
    "conv_fastpath_counts",
    "conv_gemm1x1_elected",
    "reset_conv_fastpaths",
    "fused_elementwise",
    "ffn_gateup",
    "qmatmul",
    "attention",
    "TuningCache",
    "tuning_cache",
    "tune_rejected_counts",
    "set_tuning",
]


def interpret_default() -> bool:
    """Whether the kernel wrappers run Pallas in interpret mode: on whenever
    the default backend is not a TPU, so every kernel stays parity-testable
    on a CPU.  ``REPRO_PALLAS_INTERPRET`` forces it either way
    (``chip_smoke.py`` refuses to run while it is set)."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


def _flatten_batch(x: jax.Array) -> Tuple[jax.Array, Tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _pad_axis(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --------------------------------------------------------------------------- #
# block-size tuning cache                                                      #
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class TuneEntry:
    blocks: Tuple[int, ...]
    source: str  # "default" | "swept" | "loaded"
    ms: Optional[float] = None


class TuningCache:
    """Per-shape kernel block-size cache, keyed by
    ``(op, M, N, K, dtype, format)``.

    ``resolve`` returns cached blocks when the key is known; otherwise, with
    tuning enabled *and* a runner supplied (concrete arrays, not tracers), it
    sweeps the candidate grid once, stores the winner, and returns it.  With
    tuning disabled it records + returns the seeded default, so test suites
    never pay a sweep.
    """

    #: default blocks per op: matmul family is (block_m, block_n, block_k,
    #: pipeline_depth) -- depth 1 is the compiler-scheduled grid-K path,
    #: depth >= 2 the hand-rolled double-buffered K streaming ring;
    #: bsr_matmul tunes only block_m (block_n/k come from the packed format);
    #: fused_elementwise tunes block_m (full feature dim is tile-resident)
    DEFAULTS: Dict[str, Tuple[int, ...]] = {
        "matmul": (128, 128, 128, 1),
        "bsr_matmul": (128,),
        "fused_elementwise": (128,),
        "qmatmul": (128, 128, 128, 1),
        # conv2d tunes (block_h, block_o, block_c): output rows per tile (the
        # GEMM M block is block_h * OW), output-channel lanes per tile, and
        # the tiled-K channel granularity (0 = resident full-K contraction;
        # block_c > 0 streams block_k = block_c*kh*kw K-slabs per grid step)
        "conv2d": (8, 128, 0),
    }
    #: small sweep grids; TPU lanes want the minor dims at 128 multiples
    #: (pallas_guide: f32 min tile 8x128, MXU 128x128)
    CANDIDATES: Dict[str, Tuple[Tuple[int, ...], ...]] = {
        "matmul": (
            (128, 128, 128, 1),
            (64, 128, 128, 1),
            (256, 128, 128, 1),
            (128, 256, 128, 1),
            (128, 128, 256, 1),
            # hand-pipelined double-buffered K streaming (depth-2 ring)
            (128, 128, 128, 2),
            (128, 128, 256, 2),
        ),
        "bsr_matmul": ((64,), (128,), (256,)),
        "fused_elementwise": ((64,), (128,), (256,), (512,), (1024,)),
        # int8 tiles are (32, 128)-granular; larger K blocks amortize the
        # rescale and exploit the 4x smaller weight stream
        "qmatmul": (
            (128, 128, 128, 1),
            (64, 128, 128, 1),
            (256, 128, 128, 1),
            (128, 256, 128, 1),
            (128, 128, 256, 1),
            (128, 128, 512, 1),
            # hand-pipelined ring: int8 slabs are 4x smaller, deeper K pays
            (128, 128, 128, 2),
            (128, 128, 512, 2),
        ),
        # more rows per tile amortizes the per-tap patch slicing; larger
        # block_o amortizes image residency across output channels; non-zero
        # block_c trades image residency for the tiled-K accumulator
        "conv2d": (
            (1, 128, 0),
            (2, 128, 0),
            (4, 128, 0),
            (8, 128, 0),
            (16, 128, 0),
            (4, 256, 0),
            (8, 256, 0),
            (8, 128, 64),
            (8, 128, 128),
            (4, 256, 128),
        ),
    }

    def __init__(self, enabled: Optional[bool] = None, path: Optional[str] = None):
        env = os.environ.get("REPRO_TUNE")
        self.enabled = (env not in (None, "0", "false", "False")) if enabled is None else enabled
        self.entries: Dict[str, TuneEntry] = {}
        self.sweeps = 0  # number of grid sweeps actually executed
        #: restrict sweeping to these op families (None = all); lookups and
        #: defaults still serve every family (the tune CLI's --ops filter)
        self.ops_filter: Optional[frozenset] = None
        #: per-key-family resolve accounting: hits (cached winner returned),
        #: misses (no usable entry -- default recorded or sweep triggered),
        #: sweeps (candidate grids actually timed)
        self.stats: Dict[str, Dict[str, int]] = {}
        self.path = path or os.environ.get("REPRO_TUNE_CACHE")
        if self.path and os.path.exists(self.path):
            try:
                self.load(self.path)
            except (json.JSONDecodeError, KeyError, TypeError, OSError) as e:
                # a stale/corrupt cache must never brick the import; sweeps
                # or defaults will repopulate it on the next save
                import warnings

                warnings.warn(f"ignoring unreadable tuning cache {self.path}: {e}")

    # -- keying -------------------------------------------------------------- #
    @staticmethod
    def key_nd(op: str, shape: Sequence[int], dtype: Any, fmt: str, interpret: bool) -> str:
        """Key over an arbitrary-rank shape signature: the GEMM family keys
        on ``MxNxK``, ``conv2d`` on ``NxCxHxWxOxKHxKWxS`` (batch, contracted
        input channels, spatial dims, output channels, filter taps, stride).
        interpret-mode timings measure Python, not silicon: never let them
        masquerade as (or shadow) real-hardware winners."""
        mode = "interpret" if interpret else "hw"
        dims = "x".join(str(int(d)) for d in shape)
        return f"{op}|{dims}|{jnp.dtype(dtype).name}|{fmt}|{mode}"

    @staticmethod
    def key(op: str, m: int, n: int, k: int, dtype: Any, fmt: str, interpret: bool) -> str:
        return TuningCache.key_nd(op, (m, n, k), dtype, fmt, interpret)

    # -- lookup / sweep ------------------------------------------------------ #
    def lookup(self, op, m, n, k, dtype, fmt, interpret) -> Optional[Tuple[int, ...]]:
        return self.lookup_nd(op, (m, n, k), dtype, fmt, interpret)

    def lookup_nd(self, op, shape, dtype, fmt, interpret) -> Optional[Tuple[int, ...]]:
        e = self.entries.get(self.key_nd(op, shape, dtype, fmt, interpret))
        return None if e is None else e.blocks

    def resolve(
        self,
        op: str,
        m: int,
        n: int,
        k: int,
        dtype: Any,
        fmt: str,
        interpret: bool,
        runner: Optional[Callable[..., Any]] = None,
        reps: int = 3,
        default: Optional[Tuple[int, ...]] = None,
    ) -> Tuple[int, ...]:
        return self.resolve_nd(
            op, (m, n, k), dtype, fmt, interpret, runner, reps, default
        )

    def resolve_nd(
        self,
        op: str,
        shape: Sequence[int],
        dtype: Any,
        fmt: str,
        interpret: bool,
        runner: Optional[Callable[..., Any]] = None,
        reps: int = 3,
        default: Optional[Tuple[int, ...]] = None,
    ) -> Tuple[int, ...]:
        """Cached winner for the key if one exists; else sweep (tuning
        enabled + concrete runner + op not excluded by ``ops_filter``) or
        fall back to ``default`` (the caller's shape/mode-aware seed) or the
        op family's static ``DEFAULTS`` entry."""
        key = self.key_nd(op, shape, dtype, fmt, interpret)
        stat = self.stats.setdefault(op, {"hits": 0, "misses": 0, "sweeps": 0})
        hit = self.entries.get(key)
        can_sweep = (
            self.enabled
            and runner is not None
            and (self.ops_filter is None or op in self.ops_filter)
        )
        # seeded-default entries are placeholders, not measurements: re-tune
        # them the first time a sweep is actually possible
        if hit is not None and not (can_sweep and hit.source == "default"):
            stat["hits"] += 1
            return hit.blocks
        stat["misses"] += 1
        if can_sweep:
            best, best_ms = None, float("inf")
            rejected: Dict[str, int] = {}
            for cand in self.CANDIDATES[op]:
                try:
                    jax.block_until_ready(runner(*cand))  # compile + warm
                    ts = []
                    for _ in range(reps):
                        t0 = time.perf_counter()
                        jax.block_until_ready(runner(*cand))
                        ts.append(time.perf_counter() - t0)
                    ms = float(np.median(ts)) * 1e3
                except Exception as e:
                    # invalid for this shape/backend (e.g. a tile Mosaic
                    # refuses): counted, never mistaken for "slow"
                    err = type(e).__name__
                    rejected[err] = rejected.get(err, 0) + 1
                    _metrics.registry().counter(
                        _TUNE_REJECTED_METRIC, op=op, error=err
                    ).inc()
                    continue
                if ms < best_ms:
                    best, best_ms = cand, ms
            self.sweeps += 1
            stat["sweeps"] += 1
            if best is None:
                raise RuntimeError(
                    f"every {op} tuning candidate failed for {key}: {rejected}"
                )
            self.entries[key] = TuneEntry(best, "swept", best_ms)
            return best
        default = default or self.DEFAULTS[op]
        self.entries[key] = TuneEntry(default, "default")
        return default

    # -- persistence --------------------------------------------------------- #
    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if not path:
            raise ValueError("no cache path given (arg or REPRO_TUNE_CACHE)")
        payload = {
            "version": 1,
            # defaults are placeholders (never measured): persisting them
            # would block future sweeps of those shapes in other processes
            "entries": {
                k: {"blocks": list(e.blocks), "source": e.source, "ms": e.ms}
                for k, e in self.entries.items()
                if e.source != "default"
            },
        }
        # crash-safe (utils.fileio): temp file in the target directory,
        # fsync, atomic rename -- a reader can never observe a truncated
        # JSON and an interrupted save leaves the previous file intact
        from ..utils.fileio import atomic_write_json

        return atomic_write_json(path, payload, prefix=".tune-")

    def load(self, path: str) -> "TuningCache":
        with open(path) as f:
            payload = json.load(f)
        for k, e in payload["entries"].items():
            self.entries[k] = TuneEntry(tuple(e["blocks"]), "loaded", e.get("ms"))
        return self

    def clear(self) -> None:
        self.entries.clear()
        self.sweeps = 0
        self.stats.clear()

    def stats_report(self) -> str:
        """Per-key-family resolve accounting (hits / misses / sweeps) --
        printed by the ``launch.tune`` CLI after a pre-warm pass."""
        lines = ["family,hits,misses,sweeps"]
        for op in sorted(self.stats):
            s = self.stats[op]
            lines.append(f"{op},{s['hits']},{s['misses']},{s['sweeps']}")
        return "\n".join(lines)

    def report(self) -> str:
        lines = ["op,shape,dtype,format,mode,blocks,source,ms"]
        for k in sorted(self.entries):
            op, shape, dt, fmt, mode = k.split("|")
            e = self.entries[k]
            ms = "" if e.ms is None else f"{e.ms:.3f}"
            lines.append(
                f"{op},{shape},{dt},{fmt},{mode},{'x'.join(map(str, e.blocks))},{e.source},{ms}"
            )
        return "\n".join(lines)


_TUNING = TuningCache()

#: sweep candidates that raised, by op family and exception type
_TUNE_REJECTED_METRIC = "tune_rejected_total"


def tune_rejected_counts() -> Dict[str, int]:
    """Rejected tuning-sweep candidates keyed ``"op/error"`` -- a view over
    the ``tune_rejected_total`` registry family."""
    counts = _metrics.registry().label_counts(_TUNE_REJECTED_METRIC, "op", "error")
    return {k: int(v) for k, v in counts.items()}


def tuning_cache() -> TuningCache:
    """The process-wide block-size cache consulted by matmul/bsr_matmul/
    col_matmul when block sizes are not pinned explicitly."""
    return _TUNING


def set_tuning(enabled: bool) -> TuningCache:
    _TUNING.enabled = enabled
    return _TUNING


def _concrete(*arrays) -> bool:
    """True when no argument is a tracer (sweeping requires real timing)."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def _blocks4(blocks: Sequence[int]) -> Tuple[int, int, int, int]:
    """Normalize a matmul-family blocks tuple: legacy 3-field entries (from
    pre-pipeline cache files) mean the compiler-scheduled grid-K path
    (pipeline depth 1)."""
    t = tuple(int(b) for b in blocks)
    return t if len(t) == 4 else (*t[:3], 1)


def _conv_blocks3(blocks: Sequence[int]) -> Tuple[int, int, int]:
    """Normalize a conv2d blocks tuple: legacy 2-field entries mean the
    resident full-K contraction (block_c == 0)."""
    t = tuple(int(b) for b in blocks)
    return t if len(t) == 3 else (*t[:2], 0)


def _matmul_blocked(
    x2, w, bias, activation, block_m, block_n, block_k, interpret,
    epilogue=(), sides=(), pipeline=1,
):
    m, k = x2.shape
    n = w.shape[1]
    xp = _pad_axis(_pad_axis(x2, block_m, 0), block_k, 1)
    wp = _pad_axis(_pad_axis(w, block_k, 0), block_n, 1)
    bp = None if bias is None else _pad_axis(bias, block_n, 0)
    sp = [_pad_axis(_pad_axis(s, block_m, 0), block_n, 1) for s in sides]
    return _dense_matmul(
        xp,
        wp,
        bp,
        *sp,
        activation=activation,
        epilogue=tuple(epilogue),
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        pipeline=pipeline,
        interpret=interpret,
    )[:m, :n]


def matmul(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    epilogue: Sequence[Tuple] = (),
    epilogue_sides: Sequence[jax.Array] = (),
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    pipeline: Optional[int] = None,
    interpret: Optional[bool] = None,
    _format: str = "dense",
) -> jax.Array:
    """``epilogue(act(x @ w + bias))`` for arbitrary leading batch dims via
    the fused dense Pallas kernel; pads M/N/K to block multiples and slices
    back.  ``epilogue`` is a step program (``("activation", fn)`` /
    ``("add"|"mul", slot)`` into ``epilogue_sides``, each shaped like the
    output) run on the f32 accumulator inside the kernel.

    Block sizes left as ``None`` are resolved through the tuning cache
    (cached winner for this shape if one exists, else the seeded default;
    a one-off candidate sweep when tuning is enabled on concrete arrays).
    The cached tuple's 4th field is the pipeline depth: 1 = grid-K (the
    compiler's automatic double-buffering), >= 2 = the hand-rolled DMA ring
    in :func:`~.dense_matmul.dense_matmul_pipelined_kernel`; ``pipeline``
    pins it explicitly.
    """
    interpret = interpret_default() if interpret is None else interpret
    x2, lead = _flatten_batch(x)
    m, k = x2.shape
    n = w.shape[1]
    sides2 = []
    for s in epilogue_sides:
        assert s.shape == (*lead, n) or s.shape == (m, n), (s.shape, (*lead, n))
        sides2.append(s.reshape(m, n))
    if block_m is None and block_n is None and block_k is None:
        runner = None
        if _TUNING.enabled and _concrete(x2, w, bias, *sides2):
            runner = lambda bm, bn, bk, depth=1: _matmul_blocked(
                x2, w, bias, activation, bm, bn, bk, interpret, epilogue,
                sides2, pipeline if pipeline is not None else depth,
            )
        # an epilogue'd GEMM streams extra per-tile sides (different VMEM
        # pressure): never let its swept winner alias the plain GEMM's
        fmt = (
            f"{_format}+e{len(epilogue)}s{len(sides2)}" if epilogue else _format
        )
        block_m, block_n, block_k, depth = _blocks4(_TUNING.resolve(
            "matmul", m, n, k, x2.dtype, fmt, interpret, runner
        ))
        pipeline = depth if pipeline is None else pipeline
    elif block_m is None or block_n is None or block_k is None:
        # partially pinned: fill from defaults, never from the cache -- a
        # swept winner for the free dims was timed with different pins
        dm, dn, dk, _ = TuningCache.DEFAULTS["matmul"]
        block_m, block_n, block_k = block_m or dm, block_n or dn, block_k or dk
    out = _matmul_blocked(
        x2, w, bias, activation, block_m, block_n, block_k, interpret,
        epilogue, sides2, pipeline or 1,
    )
    return out.reshape(*lead, n)


def _qmatmul_blocked(
    x2, w_q, w_scale, bias, activation, block_m, block_n, block_k, interpret,
    epilogue=(), sides=(), pipeline=1,
):
    m, k = x2.shape
    n = w_q.shape[1]
    xp = _pad_axis(_pad_axis(x2, block_m, 0), block_k, 1)
    wp = _pad_axis(_pad_axis(w_q, block_k, 0), block_n, 1)
    wsp = _pad_axis(w_scale, block_n, 0)
    bp = None if bias is None else _pad_axis(bias, block_n, 0)
    sp = [_pad_axis(_pad_axis(s, block_m, 0), block_n, 1) for s in sides]
    return _quant_matmul(
        xp,
        wp,
        wsp,
        bp,
        *sp,
        activation=activation,
        epilogue=tuple(epilogue),
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        pipeline=pipeline,
        interpret=interpret,
    )[:m, :n]


def qmatmul(
    x: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    x_scale: Optional[float] = None,
    activation: Optional[str] = None,
    epilogue: Sequence[Tuple] = (),
    epilogue_sides: Sequence[jax.Array] = (),
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    pipeline: Optional[int] = None,
    interpret: Optional[bool] = None,
    _format: str = "dense",
) -> jax.Array:
    """Quantized ``epilogue(act((x @ w_q) * scales + bias))`` for arbitrary
    leading batch dims via the INT8 Pallas kernel.

    ``w_q [K, N]`` int8 with per-output-channel ``w_scale [N]`` f32.  With
    ``x_scale`` (the calibrated static activation scale, a Python float) the
    f32 activations are quantized to int8 here and the kernel contracts
    int8 x int8 into int32 (**W8A8**; the activation scale is folded into the
    per-column rescale).  Without it, activations stay f32 and only the
    weight stream is int8, dequantized per-tile in VMEM (**W8-only** -- the
    scheme the colcompact/channelcompact pruned formats use).

    Tuned under the ``qmatmul`` cache key family: the format string carries
    the storage format *and* the scheme (``dense+w8a8``, ``colcompact+w8``,
    ...) plus the usual ``+e{steps}s{sides}`` epilogue suffix -- int8 streams
    change VMEM residency and arithmetic width, so a winner never aliases the
    f32 ``matmul`` family.
    """
    from ..quant.qtensor import quantize_array  # local: quant layer is optional

    interpret = interpret_default() if interpret is None else interpret
    x2, lead = _flatten_batch(x)
    m, k = x2.shape
    n = w_q.shape[1]
    sides2 = []
    for s in epilogue_sides:
        assert s.shape == (*lead, n) or s.shape == (m, n), (s.shape, (*lead, n))
        sides2.append(s.reshape(m, n))
    w_scale = w_scale.astype(jnp.float32)
    if x_scale is not None:
        # W8A8: statically-scaled int8 activations; kernel sees one combined
        # per-column rescale (x_scale * w_scale[n])
        x2 = quantize_array(x2, jnp.float32(x_scale))
        w_scale = w_scale * jnp.float32(x_scale)
    scheme = "w8" if x_scale is None else "w8a8"
    if block_m is None and block_n is None and block_k is None:
        runner = None
        if _TUNING.enabled and _concrete(x2, w_q, w_scale, bias, *sides2):
            runner = lambda bm, bn, bk, depth=1: _qmatmul_blocked(
                x2, w_q, w_scale, bias, activation, bm, bn, bk, interpret,
                epilogue, sides2, pipeline if pipeline is not None else depth,
            )
        fmt = f"{_format}+{scheme}"
        if epilogue:
            fmt += f"+e{len(epilogue)}s{len(sides2)}"
        block_m, block_n, block_k, depth = _blocks4(_TUNING.resolve(
            "qmatmul", m, n, k, x2.dtype, fmt, interpret, runner
        ))
        pipeline = depth if pipeline is None else pipeline
    elif block_m is None or block_n is None or block_k is None:
        dm, dn, dk, _ = TuningCache.DEFAULTS["qmatmul"]
        block_m, block_n, block_k = block_m or dm, block_n or dn, block_k or dk
    out = _qmatmul_blocked(
        x2, w_q, w_scale, bias, activation, block_m, block_n, block_k,
        interpret, epilogue, sides2, pipeline or 1,
    )
    return out.reshape(*lead, n)


# --------------------------------------------------------------------------- #
# implicit-GEMM conv2d                                                          #
# --------------------------------------------------------------------------- #

#: conv2d lowering decisions live in the metrics registry, counted at trace
#: time under jit:
#:
#: * ``conv_fallback_total{reason}`` -- calls lowered through lax.conv
#:   instead of the Pallas kernel (the documented fallback matrix: groups /
#:   dilation / degenerate output / VMEM overflow).
#: * ``conv_fastpath_total{scheme}`` -- calls elected onto the 1x1
#:   direct-GEMM fast path (im2col bypassed, lowered to dense/quant
#:   matmul); an election is a lowering decision, not a fallback.
#:
#: The accessors below are back-compat *views* over those families.
_CONV_FALLBACK_METRIC = "conv_fallback_total"
_CONV_FASTPATH_METRIC = "conv_fastpath_total"


def conv_fallback_counts() -> Dict[str, int]:
    """The conv2d fallback counters (reason -> count) -- the "no lax.conv
    except documented fallbacks" acceptance probe.  A view over the
    ``conv_fallback_total`` registry family."""
    counts = _metrics.registry().label_counts(_CONV_FALLBACK_METRIC, "reason")
    return {k: int(v) for k, v in counts.items()}


def reset_conv_fallbacks() -> None:
    _metrics.registry().reset(_CONV_FALLBACK_METRIC)


def conv_fastpath_counts() -> Dict[str, int]:
    """The 1x1 direct-GEMM election counters (scheme -> count) -- a view
    over the ``conv_fastpath_total`` registry family."""
    counts = _metrics.registry().label_counts(_CONV_FASTPATH_METRIC, "scheme")
    return {k: int(v) for k, v in counts.items()}


def reset_conv_fastpaths() -> None:
    _metrics.registry().reset(_CONV_FASTPATH_METRIC)


def conv_gemm1x1_elected(kh: int, kw: int, groups: int, padding, c: int) -> bool:
    """True when a conv lowers through the 1x1 direct-GEMM fast path: unit
    taps, ungrouped, live input channels, and padding that adds no border
    (SAME == VALID for 1x1 taps; explicit pads must be all-zero).  Dilation
    is irrelevant for a unit tap, so it never blocks election.  Shared by
    :func:`conv2d` and :meth:`ExecutionPlan.memory_estimate` (an elected
    step owns no conv-kernel VMEM workspace)."""
    if kh != 1 or kw != 1 or groups != 1 or c <= 0:
        return False
    if isinstance(padding, str):
        return padding in ("SAME", "VALID")
    try:
        (a, b), (c2, d) = padding
        return int(a) == int(b) == int(c2) == int(d) == 0
    except (TypeError, ValueError):
        return False


def _hw_tiled_block_cs(c: int) -> List[int]:
    """The tiled-K granularities a TPU can compile for ``c`` contracted
    channels: a block's minor dim must be a 128-lane multiple (or the whole
    dim), and a block as wide as ``c`` is the resident path."""
    return sorted(
        {
            cand[2] for cand in TuningCache.CANDIDATES["conv2d"]
            if len(cand) > 2 and cand[2] and cand[2] % 128 == 0 and cand[2] < c
        }
    )


def conv_fallback_reason(
    c: int,
    h: int,
    w: int,
    kh: int,
    kw: int,
    stride: int,
    padding,
    *,
    groups: int = 1,
    dilation: int = 1,
    interpret: bool,
    x_itemsize: int = 4,
    w_itemsize: int = 4,
    block_h: Optional[int] = None,
    block_o: Optional[int] = None,
    block_c: Optional[int] = None,
    n_sides: int = 0,
) -> Optional[str]:
    """The conv2d fallback matrix, shared by the :func:`conv2d` wrapper and
    :meth:`ExecutionPlan.memory_estimate` (a step that lowers through
    lax.conv has no Pallas VMEM workspace).  ``c`` is the *contracted*
    channel count.  The VMEM guard asks whether any resolvable configuration
    fits the kernel's scoped-VMEM budget (:data:`conv2d.VMEM_LIMIT_BYTES`,
    the ``vmem_limit_bytes`` it compiles with) in Mosaic's tiled layout:
    pinned blocks are honored verbatim; otherwise it evaluates the default
    (block_h, block_o) resident, then at the smallest lane-aligned tiled-K
    granularity.  ``tests/test_tpu_compile.py`` compiles the admitted app
    shapes for the v5e."""
    if groups != 1:
        return "groups"
    if dilation != 1:
        return "dilation"
    if not isinstance(padding, str):
        try:
            (a, b), (c2, d) = padding
            if min(int(a), int(b), int(c2), int(d)) < 0:
                return "padding"  # lax allows negative (cropping) pads; we don't
        except (TypeError, ValueError):
            return "padding"
    try:
        oh, ow = conv_out_hw(h, w, kh, kw, stride, padding)
    except (TypeError, ValueError):
        return "padding"
    if oh < 1 or ow < 1:
        return "degenerate"
    if not interpret and stride > 1 and x_itemsize < 4:
        return "stride_narrow"  # Mosaic's strided load takes 32-bit data only
    if not interpret:
        dh, do_, _ = TuningCache.DEFAULTS["conv2d"]
        bh = block_h or dh
        bo = block_o or do_
        if block_c is not None:
            c_options = [block_c]
        else:
            # resident first (cheapest when it fits), then the smallest
            # tiled-K granularity the chip can compile
            c_options = [0] + _hw_tiled_block_cs(c)[:1]
        fits = any(
            conv_vmem_workspace(
                c, h, w, kh, kw, stride, padding, bh, bo, bc,
                x_itemsize=x_itemsize, w_itemsize=w_itemsize, n_sides=n_sides,
            )["total"] <= _CONV_VMEM_LIMIT
            for bc in c_options
        )
        if not fits:
            return "vmem"
    return None


def _conv_default_blocks(
    c: int,
    h: int,
    w: int,
    kh: int,
    kw: int,
    stride: int,
    padding,
    x_itemsize: int,
    w_itemsize: int,
    interpret: bool,
    n_sides: int = 0,
) -> Tuple[int, int, int]:
    """Shape-aware conv default: the seeded (block_h, block_o) with the
    cheapest K granularity that fits VMEM -- resident when possible, else
    the largest fitting lane-aligned tiled-K candidate (fewer grid steps),
    else the smallest.  Interpret mode has no VMEM, so it always stays
    resident."""
    dh, do_, _ = TuningCache.DEFAULTS["conv2d"]
    if interpret:
        return (dh, do_, 0)
    tiled = _hw_tiled_block_cs(c)
    for bc in (0, *reversed(tiled)):
        total = conv_vmem_workspace(
            c, h, w, kh, kw, stride, padding, dh, do_, bc,
            x_itemsize=x_itemsize, w_itemsize=w_itemsize, n_sides=n_sides,
        )["total"]
        if total <= _CONV_VMEM_LIMIT:
            return (dh, do_, bc)
    return (dh, do_, tiled[0] if tiled else 0)  # guard rejects this case


def _conv2d_fallback(
    x, w, bias, *, stride, padding, kept, w_scale, x_scale, groups, dilation,
    activation, epilogue, sides,
):
    """lax.conv path for configs outside the kernel's matrix -- same math as
    the reference handlers (dequant / fake-quant / channel gather / jnp
    epilogue), so a fallback never changes results, only the engine."""
    if kept is not None:
        x = jnp.take(x, kept, axis=1)
    if w.dtype == jnp.int8:
        w = w.astype(jnp.float32) * w_scale.astype(jnp.float32)[:, None, None, None]
        if x_scale is not None:
            from ..quant.qtensor import fake_quant  # local: quant is optional

            x = fake_quant(x.astype(jnp.float32), jnp.float32(x_scale))
    y = _ref.conv2d_ref(
        x, w, bias, stride=stride, padding=padding, groups=groups,
        dilation=dilation, activation=activation, out_dtype=jnp.float32,
    )
    if epilogue:
        y = _ref.apply_steps_ref(y, epilogue, [s.astype(jnp.float32) for s in sides])
    return y.astype(x.dtype)


def _conv2d_1x1_gemm(
    x, w, bias, *, stride, kept, w_scale, x_scale, activation, epilogue,
    sides, interpret, fmt, is_q,
):
    """The 1x1 direct-GEMM fast path: a unit-tap conv with no border padding
    is ``y[n, :, i, j] = W @ x[n, :, i*s, j*s]`` -- a plain GEMM over the
    ``N*OH*OW`` pixel axis.  The NCHW tensor is reshaped NHWC -> [pixels, C]
    (strides subsample the grid first; SAME and VALID coincide for 1x1
    taps), the OIHW filter collapses to [C, O], and the conv's whole fused
    program -- bias, activation, epilogue steps with their side operands --
    rides the dense/quant matmul kernel unchanged.  Keys under the
    ``conv1x1.{fmt}`` matmul-family format, never aliasing a plain GEMM's
    winner (the pixel-axis M has different tuning pressure)."""
    if kept is not None:
        x = jnp.take(x, kept, axis=1)
    if stride > 1:
        x = x[:, :, ::stride, ::stride]
    nb, c, oh, ow = x.shape
    o = w.shape[0]
    assert w.shape[1] == c, (w.shape, c)
    for s in sides:
        assert s.shape == (nb, o, oh, ow), (s.shape, (nb, o, oh, ow))
    xm = x.transpose(0, 2, 3, 1).reshape(nb * oh * ow, c)
    wm = w.reshape(o, c).T  # OIHW unit taps -> [C, O]
    sm = [s.transpose(0, 2, 3, 1).reshape(nb * oh * ow, o) for s in sides]
    if is_q:
        y = qmatmul(
            xm, wm, w_scale, bias, x_scale=x_scale, activation=activation,
            epilogue=epilogue, epilogue_sides=sm, interpret=interpret,
            _format=f"conv1x1.{fmt}",
        )
    else:
        y = matmul(
            xm, wm, bias, activation=activation, epilogue=epilogue,
            epilogue_sides=sm, interpret=interpret, _format=f"conv1x1.{fmt}",
        )
    return y.reshape(nb, oh, ow, o).transpose(0, 3, 1, 2)


def conv2d(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    stride: int = 1,
    padding: str = "SAME",
    kept: Optional[jax.Array] = None,
    w_scale: Optional[jax.Array] = None,
    x_scale: Optional[float] = None,
    groups: int = 1,
    dilation: int = 1,
    activation: Optional[str] = None,
    epilogue: Sequence[Tuple] = (),
    epilogue_sides: Sequence[jax.Array] = (),
    block_h: Optional[int] = None,
    block_o: Optional[int] = None,
    block_c: Optional[int] = None,
    gemm_1x1: bool = True,
    interpret: Optional[bool] = None,
    _format: Optional[str] = None,
) -> jax.Array:
    """``epilogue(act(conv2d(x, w) + bias))`` through the tiled Pallas
    implicit-GEMM kernel.  ``x [N, C, H, W]`` NCHW, ``w [O, C', kh, kw]``
    OIHW, SAME/VALID ``padding``, square ``stride``.

    Scheme selection (at lowering time, reflected in the tuning key):

    * f32 ``w`` -> **dense** f32 accumulation.
    * ``kept`` (surviving-input-channel indices from channel/column pruning)
      -> **channel-pruned**: ``x`` is gathered to the live channels first, so
      the implicit GEMM contracts only ``C' = len(kept)`` of K.
    * int8 ``w`` + ``w_scale[O]`` -> **INT8**: with ``x_scale`` (calibrated
      static activation scale) activations quantize to int8 and the MXU
      contracts int8 x int8 into int32 (**W8A8**); without it the weight
      tiles dequantize in VMEM against f32 activations (**W8-only**).

    ``epilogue`` is the usual step program (``("activation", fn)`` /
    ``("add"|"mul", slot)`` into ``epilogue_sides``, each shaped like the
    NCHW output), run on the f32 accumulator inside the kernel.

    **1x1 fast path** (:func:`conv_gemm1x1_elected`, counted per scheme in
    :func:`conv_fastpath_counts`): a unit-tap ungrouped conv with no border
    padding is exactly a GEMM over the pixel axis -- im2col is bypassed and
    the call lowers to :func:`matmul` / :func:`qmatmul` (NHWC reshape;
    strides become a spatial subsample) with the conv's full epilogue
    program, keyed under the ``conv1x1.{fmt}`` matmul-family format.
    Election happens at lowering time, before the fallback matrix; pinning
    any conv block size or ``gemm_1x1=False`` opts back into the im2col
    kernel.

    Fallback matrix (auto-routed through ``lax.conv``, bit-identical math,
    counted in :func:`conv_fallback_counts`): ``groups != 1``,
    ``dilation != 1``, malformed/negative explicit padding, degenerate
    output (``OH*OW < 1``), or -- on real hardware only -- a strided conv
    over int8 (W8A8) activations, or a per-step VMEM
    working set (Mosaic's tiled layout, double-buffered blocks) above
    :data:`conv2d.VMEM_LIMIT_BYTES` at every resolvable K granularity.  The
    whole padded image of one batch element is resident, so frames much
    above 256x256 trip this.

    Block sizes left as ``None`` resolve through the tuning cache under the
    ``conv2d|NxCxHxWxOxKHxKWxS|{dtype}|{fmt}+{scheme}[+valid|+p..][+e..s..]|{mode}``
    key family (``(block_h, block_o, block_c)``: output rows x output
    channels per tile, plus the tiled-K channel granularity -- 0 keeps the
    whole image resident, else ``block_k = block_c*kh*kw`` of the GEMM K
    streams per grid step; SAME -- the canonical geometry -- keys without a
    padding suffix).  The default is shape-aware: resident when the working
    set fits VMEM, else the largest fitting ``block_c`` candidate.
    """
    interpret = interpret_default() if interpret is None else interpret
    epilogue = tuple(tuple(s) for s in epilogue)
    sides = tuple(epilogue_sides)
    nb, c_in, h, w_in = x.shape
    o, cw, kh, kw_ = w.shape
    is_q = w.dtype == jnp.int8
    if is_q and w_scale is None:
        raise ValueError("int8 conv weights need w_scale")
    if x_scale is not None and not is_q:
        raise ValueError("x_scale (W8A8) requires int8 weights")
    scheme = "f32" if not is_q else ("w8a8" if x_scale is not None else "w8")
    fmt = _format or ("channelcompact" if kept is not None else "dense")
    c_live = int(kept.shape[0]) if kept is not None else c_in
    if (
        gemm_1x1
        and block_h is None and block_o is None and block_c is None
        and conv_gemm1x1_elected(kh, kw_, groups, padding, c_live)
    ):
        _metrics.registry().counter(_CONV_FASTPATH_METRIC, scheme=scheme).inc()
        return _conv2d_1x1_gemm(
            x, w, bias, stride=stride, kept=kept, w_scale=w_scale,
            x_scale=x_scale, activation=activation, epilogue=epilogue,
            sides=sides, interpret=interpret, fmt=fmt, is_q=is_q,
        )
    reason = conv_fallback_reason(
        c_live,
        h, w_in, kh, kw_, stride, padding,
        groups=groups, dilation=dilation, interpret=interpret,
        x_itemsize=1 if scheme == "w8a8" else x.dtype.itemsize,
        w_itemsize=w.dtype.itemsize, block_h=block_h, block_o=block_o,
        block_c=block_c, n_sides=len(sides),
    )
    if reason is not None:
        _metrics.registry().counter(_CONV_FALLBACK_METRIC, reason=reason).inc()
        return _conv2d_fallback(
            x, w, bias, stride=stride, padding=padding, kept=kept,
            w_scale=w_scale, x_scale=x_scale, groups=groups, dilation=dilation,
            activation=activation, epilogue=epilogue, sides=sides,
        )

    oh, ow = conv_out_hw(h, w_in, kh, kw_, stride, padding)
    for s in sides:
        assert s.shape == (nb, o, oh, ow), (s.shape, (nb, o, oh, ow))
    if kept is not None:
        x = jnp.take(x, kept, axis=1)
    c = x.shape[1]
    assert c == cw, (x.shape, w.shape)
    if c == 0:
        # every input channel pruned away: the output is pure epilogue math
        # over the bias (the empty contraction contributes zeros)
        y = jnp.zeros((nb, o, oh, ow), jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)[None, :, None, None]
        y = _ref._ACT[activation](y)
        if epilogue:
            y = _ref.apply_steps_ref(y, epilogue, [s.astype(jnp.float32) for s in sides])
        return y.astype(x.dtype)

    x2 = x
    out_dtype = x.dtype
    if scheme == "w8a8":
        from ..quant.qtensor import quantize_array  # local: quant is optional

        x2 = quantize_array(x2.astype(jnp.float32), jnp.float32(x_scale))
        out_dtype = jnp.float32
    ws_vec = None
    if is_q:
        ws_vec = w_scale.astype(jnp.float32)
        if scheme == "w8a8":
            ws_vec = ws_vec * jnp.float32(x_scale)
        out_dtype = jnp.float32
    pt, pl_ = conv_pad_hw(h, w_in, kh, kw_, stride, padding)

    def run(bh, bo, bc=0):
        ohp = -(-oh // bh) * bh
        hpad = (ohp - 1) * stride + kh
        wpad = (ow - 1) * stride + kw_
        # one HBM layout pass: NCHW -> NHWC + crop/zero-pad to the exact
        # span the taps touch (this is *padding*, never the im2col matrix --
        # patches materialize in VMEM only).  A VALID conv may leave an
        # unconsumed input tail, so crop before padding.
        h_used = min(h, hpad - pt)
        w_used = min(w_in, wpad - pl_)
        xt = jnp.pad(
            x2.transpose(0, 2, 3, 1)[:, :h_used, :w_used],
            ((0, 0), (pt, hpad - pt - h_used), (pl_, wpad - pl_ - w_used), (0, 0)),
        )
        wt = w.transpose(2, 3, 1, 0).reshape(kh * kw_, c, o)
        if bc:
            # tiled-K: zero-pad channels to a block_c multiple (zero slabs
            # contribute nothing to the accumulator, int8 included)
            xt = _pad_axis(xt, bc, 3)
            wt = _pad_axis(wt, bc, 1)
        wt = _pad_axis(wt, bo, 2)
        op_ = wt.shape[2]
        wsp = None if ws_vec is None else _pad_axis(ws_vec, bo, 0)
        bp = None if bias is None else _pad_axis(bias, bo, 0)
        sp = []
        for s in sides:
            st = jnp.pad(
                s.transpose(0, 2, 3, 1),
                ((0, 0), (0, ohp - oh), (0, 0), (0, op_ - o)),
            )
            sp.append(st.reshape(nb * ohp * ow, op_))
        out2 = _conv2d_gemm(
            xt, wt, wsp, bp, *sp,
            stride=stride, kh=kh, kw=kw_,
            activation=activation, epilogue=epilogue,
            block_h=bh, block_o=bo, block_c=bc,
            interpret=interpret, out_dtype=out_dtype,
        )
        return (
            out2.reshape(nb, ohp, ow, op_)[:, :oh, :, :o].transpose(0, 3, 1, 2)
        )

    if block_h is None and block_o is None and block_c is None:
        runner = None
        if _TUNING.enabled and _concrete(x2, w, bias, w_scale, *sides):
            runner = run
        # SAME (canonical) keys bare; VALID / explicit pads suffix the fmt --
        # same dims, different output geometry must never share a winner
        fmtkey = f"{fmt}+{scheme}" + conv_padding_token(padding)
        if epilogue:
            fmtkey += f"+e{len(epilogue)}s{len(sides)}"
        x_item = 1 if scheme == "w8a8" else x.dtype.itemsize
        block_h, block_o, block_c = _conv_blocks3(_TUNING.resolve_nd(
            "conv2d", (nb, c, h, w_in, o, kh, kw_, stride), x2.dtype, fmtkey,
            interpret, runner,
            default=_conv_default_blocks(
                c, h, w_in, kh, kw_, stride, padding, x_item,
                w.dtype.itemsize, interpret, n_sides=len(sides),
            ),
        ))
    elif block_h is None or block_o is None or block_c is None:
        dh, do_, dc = TuningCache.DEFAULTS["conv2d"]
        block_h, block_o = block_h or dh, block_o or do_
        block_c = dc if block_c is None else block_c
    return run(block_h, block_o, block_c)


def fused_elementwise(
    x: jax.Array,
    sides: Sequence[jax.Array] = (),
    steps: Sequence[Tuple] = (),
    norm_params: Sequence[Tuple[jax.Array, jax.Array]] = (),
    *,
    block_m: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Run a fused elementwise step program over ``x`` in one Pallas pass.

    ``x`` has any leading batch dims; steps operate on the flattened
    ``[M, D]`` view (D = last dim, the layer-norm axis).  ``sides`` must
    match ``x``'s shape exactly (the tiled kernel streams them per-block);
    ``norm_params`` is one (scale[D], bias[D]) pair per ``("norm", slot,
    eps)`` step.  One HBM read + write total instead of one per step.

    ``block_m=None`` consults the tuning cache under the
    ``fused_elementwise`` op key (M x D x n_steps).
    """
    interpret = interpret_default() if interpret is None else interpret
    d = x.shape[-1]
    for s in sides:
        assert s.shape == x.shape, (s.shape, x.shape)
    x2, lead = _flatten_batch(x)
    m = x2.shape[0]
    steps = tuple(tuple(s) for s in steps)

    def run(bm):
        xp = _pad_axis(_pad_axis(x2, bm, 0), 128, 1)
        sp = [_pad_axis(_pad_axis(s.reshape(m, d), bm, 0), 128, 1) for s in sides]
        nps = []
        for scale, bias in norm_params:
            nps.append(_pad_axis(scale, 128, 0).reshape(1, -1))
            nps.append(_pad_axis(bias, 128, 0).reshape(1, -1))
        return _fused_elementwise(
            xp,
            *sp,
            *nps,
            steps=steps,
            n_norms=len(norm_params),
            d_true=d,
            block_m=bm,
            interpret=interpret,
        )[:m, :d]

    if block_m is None:
        runner = None
        flat_norms = [a for pair in norm_params for a in pair]
        if _TUNING.enabled and _concrete(x2, *sides, *flat_norms):
            runner = lambda bm: run(bm)
        # side/norm counts change per-tile VMEM residency: same-shape
        # programs with different operand counts must not share a winner
        fmt = f"ew+s{len(sides)}n{len(norm_params)}"
        # interpret mode pays ~1 ms of Python per grid step, which swamps
        # this memory-bound kernel at the 128-row default (the 0.13x/0.50x
        # regression profiled in BENCH_fusion.json): seed a single full-M
        # tile there -- one grid step -- and keep the VMEM-sized 128-row
        # default for real hardware
        default = ((-(-m // 8) * 8,) if interpret else None)
        (block_m,) = _TUNING.resolve(
            "fused_elementwise", m, d, len(steps), x2.dtype, fmt, interpret,
            runner, default=default,
        )
    return run(block_m).reshape(x.shape)


def bsr_matmul(
    x: jax.Array,
    values: jax.Array,
    block_rows: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    epilogue: Sequence[Tuple] = (),
    epilogue_sides: Sequence[jax.Array] = (),
    block_m: Optional[int] = None,
    bands: Optional[Sequence[Tuple[int, int, int]]] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Block-sparse ``epilogue(act(x @ W + bias))`` over PBCSR-packed weights.

    ``bands`` (from the reorder pass): sequence of ``(start, stop, count)``
    over output block-columns; one pallas_call per band with exact trip count
    ``count``.  Without bands, a single call pads every column to the global
    max count.  ``epilogue`` is the same step program as :func:`matmul`,
    executed on the f32 accumulator inside each band's kernel (sides are
    sliced per band and streamed per output tile).  ``block_m=None``
    consults the tuning cache -- an epilogue'd call keys separately
    (``pbcsr+e{steps}s{sides}``) since the extra side streams change VMEM
    residency.
    """
    interpret = interpret_default() if interpret is None else interpret
    x2, lead = _flatten_batch(x)
    m, k = x2.shape
    nb, s, bm, bn = values.shape
    n = nb * bn
    epilogue = tuple(tuple(st) for st in epilogue)
    sides2 = []
    for sv in epilogue_sides:
        assert sv.shape == (*lead, n) or sv.shape == (m, n), (sv.shape, (*lead, n))
        sides2.append(sv.reshape(m, n))

    def compute(block_m):
        xp = _pad_axis(x2, block_m, 0)
        sp = [_pad_axis(sv, block_m, 0) for sv in sides2]

        def run(vals, rows, bias_slice, side_slices):
            return _bsr_matmul(
                xp,
                vals,
                rows,
                bias_slice,
                *side_slices,
                activation=activation,
                epilogue=epilogue,
                block_m=block_m,
                interpret=interpret,
            )

        if not bands:
            return run(values, block_rows, bias, sp)
        pieces = []
        for start, stop, count in bands:
            if stop <= start:
                continue
            cols = slice(start, stop)
            side_slices = [sv[:, start * bn : stop * bn] for sv in sp]
            if count == 0:
                # empty band: output is pure epilogue (bias/activation of 0)
                z = jnp.zeros((xp.shape[0], (stop - start) * bn), jnp.float32)
                if bias is not None:
                    z = z + bias[start * bn : stop * bn].astype(jnp.float32)
                z = _ref._ACT[activation](z)
                if epilogue:
                    z = _ref.apply_steps_ref(
                        z, epilogue, [sl.astype(jnp.float32) for sl in side_slices]
                    )
                pieces.append(z.astype(x.dtype))
                continue
            pieces.append(
                run(
                    values[cols, :count],
                    block_rows[cols, :count],
                    None if bias is None else bias[start * bn : stop * bn],
                    side_slices,
                )
            )
        return jnp.concatenate(pieces, axis=-1)

    if block_m is None:
        runner = None
        if _TUNING.enabled and _concrete(x2, values, block_rows, bias, *sides2):
            runner = compute
        fmt = "pbcsr"
        if epilogue:
            fmt += f"+e{len(epilogue)}s{len(sides2)}"
        (block_m,) = _TUNING.resolve(
            "bsr_matmul", m, n, k, x2.dtype, fmt, interpret, runner
        )
    out = compute(block_m)
    return out[:m].reshape(*lead, n)


def col_matmul(
    x: jax.Array,
    values: jax.Array,
    kept: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    epilogue: Sequence[Tuple] = (),
    epilogue_sides: Sequence[jax.Array] = (),
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Column-pruned ``act(x @ W + bias)``: static input gather (XLA) + the
    strictly smaller fused dense GEMM (Pallas), with the same fused
    ``epilogue`` program as :func:`matmul`.  ``values [K_kept, N]``.
    Tuned under its own ``colcompact`` cache key (the gathered K differs
    from the dense layer's)."""
    xg = jnp.take(x, kept, axis=-1)
    return matmul(
        xg, values, bias, activation=activation,
        epilogue=epilogue, epilogue_sides=epilogue_sides, interpret=interpret,
        _format="colcompact",
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
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused ``act(x@Wg) * (x@Wu)`` with padding handling."""
    interpret = interpret_default() if interpret is None else interpret
    x2, lead = _flatten_batch(x)
    m, k = x2.shape
    f = w_gate.shape[1]
    xp = _pad_axis(_pad_axis(x2, block_m, 0), block_k, 1)
    wgp = _pad_axis(_pad_axis(w_gate, block_k, 0), block_n, 1)
    wup = _pad_axis(_pad_axis(w_up, block_k, 0), block_n, 1)
    out = _ffn_gateup(
        xp,
        wgp,
        wup,
        activation=activation,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        interpret=interpret,
    )[:m, :f]
    return out.reshape(*lead, f)


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    kv_lengths: Optional[jax.Array] = None,
    *, causal: bool = True,
    scale=None, block_q: int = 128, block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention over [B, H, S, d] (pads S to block multiples).

    ``kv_lengths [B]`` masks each row to its valid KV prefix (slots >= length
    never attract probability mass) -- the paged-KV path, where Skv is the
    gathered page span, not the live length.
    """
    interpret = interpret_default() if interpret is None else interpret
    sq, skv = q.shape[2], k.shape[2]
    qp = _pad_axis(q, block_q, 2)
    kp = _pad_axis(k, block_k, 2)
    vp = _pad_axis(v, block_k, 2)
    # padded KV columns must not attract probability mass: causal masking
    # handles the tail whenever sq == skv; kv_lengths masks explicitly; for
    # the remaining cross/kv-padded cases require block-aligned shapes.
    assert causal or kv_lengths is not None or (
        sq % block_q == 0 and skv % block_k == 0
    ), "non-causal attention requires block-aligned shapes or kv_lengths"
    out = _flash_attention(
        qp, kp, vp, kv_lengths, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out[:, :, :sq]
