"""Shared neural-net layers (pure JAX, params = nested dicts).

``PrunedLinear`` is the integration point of the paper's technique: one layer
type whose *execution mode* is chosen by the compiler layer --

* ``dense``   plain ``x @ w`` (XLA native; dry-run baseline),
* ``masked``  ``x @ (w * mask)`` (ADMM training / masked fine-tune),
* ``bsr``     packed PBCSR blocks via the Pallas block-sparse kernel,
* ``colpack`` ColumnCompact gather + smaller dense GEMM.

Param init functions return nested dicts; ``repro.models.sharding`` assigns
PartitionSpecs by path pattern.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kops

__all__ = [
    "init_linear",
    "linear",
    "init_rmsnorm",
    "rmsnorm",
    "init_layernorm",
    "layernorm",
    "init_embedding",
    "embed",
    "rope_freqs",
    "apply_rope",
    "init_conv1d",
    "causal_conv1d",
    "conv1d_step",
]

Array = jax.Array
Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# linear (the pruned workhorse)                                                #
# --------------------------------------------------------------------------- #


def init_linear(
    key: Array, d_in: int, d_out: int, *, bias: bool = False, dtype=jnp.bfloat16,
    scale: Optional[float] = None,
) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p: Params = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(
    p: Params,
    x: Array,
    *,
    mode: str = "dense",
    activation: Optional[str] = None,
    use_pallas: bool = False,
) -> Array:
    """Apply a (possibly pruned) linear layer.

    ``mode`` selects the execution engine; packed modes expect the packed
    params produced by the compiler layer (values/kept or values/block_rows).
    ``use_pallas`` routes dense/masked through the fused Pallas matmul
    (real-TPU path); default jnp keeps CPU tests fast and the dry-run HLO
    clean for XLA fusion analysis.
    """
    if mode in ("dense", "masked"):
        w = p["w"]
        if mode == "masked":
            w = w * p["mask"].astype(w.dtype)
        if use_pallas:
            return kops.matmul(x, w, p.get("b"), activation=activation)
        y = x @ w
        if "b" in p:
            y = y + p["b"]
        return _act(y, activation)
    if mode == "bsr":
        return kops.bsr_matmul(
            x, p["values"], p["block_rows"], p.get("b"),
            activation=activation, bands=p.get("bands"),
        )
    if mode == "bsr_xla":
        # XLA-native block-sparse execution (GSPMD-shardable; used by the
        # dry-run/pjit path where a Pallas custom-call cannot lower on CPU):
        # gather the x block-rows each output block-column needs, one einsum.
        # FLOPs scale with density exactly like the Pallas kernel.
        values, rows = p["values"], p["block_rows"]  # [Nb,S,bm,bn], [Nb,S]
        nb, s, bm, bn = values.shape
        lead = x.shape[:-1]
        xb = x.reshape(*lead, x.shape[-1] // bm, bm)
        xg = jnp.take(xb, jnp.maximum(rows, 0), axis=-2)  # [..., Nb, S, bm]
        y = jnp.einsum("...jsb,jsbn->...jn", xg, values)
        y = y.reshape(*lead, nb * bn)
        if "b" in p:
            y = y + p["b"]
        return _act(y, activation)
    if mode == "colpack":
        return kops.col_matmul(
            x, p["values"], p["kept"], p.get("b"), activation=activation
        )
    if mode == "colpack_xla":
        y = jnp.take(x, p["kept"], axis=-1) @ p["values"]
        if "b" in p:
            y = y + p["b"]
        return _act(y, activation)
    raise ValueError(f"unknown linear mode {mode!r}")


def init_pruned_linear(
    key: Array,
    d_in: int,
    d_out: int,
    *,
    exec_mode: str,
    sparsity: float,
    bm: int = 128,
    bn: int = 128,
    bias: bool = False,
    dtype=jnp.bfloat16,
) -> Params:
    """Packed-parameter init for the sparse execution modes.

    Synthetic-but-valid packing (kept indices / block rows are deterministic
    stripes): shapes are what a real ADMM->compiler pipeline would emit, so
    dry-run lowering and CPU smoke execution both work.
    """
    scale = 1.0 / math.sqrt(d_in)
    if exec_mode in ("colpack", "colpack_xla"):
        k_kept = max(1, int(round(d_in * (1.0 - sparsity))))
        p: Params = {
            "values": (jax.random.normal(key, (k_kept, d_out), jnp.float32) * scale).astype(dtype),
            "kept": jnp.arange(k_kept, dtype=jnp.int32) * (d_in // k_kept),
        }
    elif exec_mode in ("bsr", "bsr_xla"):
        kb, nb = d_in // bm, d_out // bn
        s = max(1, int(round(kb * (1.0 - sparsity))))
        p = {
            "values": (jax.random.normal(key, (nb, s, bm, bn), jnp.float32) * scale).astype(dtype),
            # stripe pattern: block-column j reads rows (j+i) % kb
            "block_rows": (
                (jnp.arange(nb, dtype=jnp.int32)[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :])
                % kb
            ),
        }
    else:
        raise ValueError(exec_mode)
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def _act(x: Array, name: Optional[str]) -> Array:
    if name is None:
        return x
    return {"relu": jax.nn.relu, "gelu": jax.nn.gelu, "silu": jax.nn.silu,
            "tanh": jnp.tanh}[name](x)


# --------------------------------------------------------------------------- #
# norms                                                                        #
# --------------------------------------------------------------------------- #


def init_rmsnorm(d: int, dtype=jnp.bfloat16) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * p["scale"]


def init_layernorm(d: int, dtype=jnp.bfloat16) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * p["scale"] + p["bias"]


# --------------------------------------------------------------------------- #
# embedding                                                                    #
# --------------------------------------------------------------------------- #


def init_embedding(key: Array, vocab: int, d: int, dtype=jnp.bfloat16) -> Params:
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)}


def embed(p: Params, tokens: Array) -> Array:
    """Row gather ``table[tokens]``.  On a mesh with explicit axes the
    gather's output sharding is ambiguous (vocab-sharded table, batch-
    sharded ids), so it is pinned to the ids' sharding with the feature
    axis replicated."""
    sh = jax.typeof(tokens).sharding
    if jax.sharding.AxisType.Explicit not in sh.mesh.axis_types:
        return jnp.take(p["table"], tokens, axis=0)
    out = jax.sharding.NamedSharding(sh.mesh, jax.sharding.PartitionSpec(*sh.spec, None))
    return p["table"].at[tokens].get(out_sharding=out)


# --------------------------------------------------------------------------- #
# RoPE                                                                         #
# --------------------------------------------------------------------------- #


def rope_freqs(head_dim: int, theta: float = 10000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: [..., S, H, dh]; positions: [..., S] (broadcastable)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # [dh/2]
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # [..., S, 1, dh/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# causal depthwise conv1d (mamba / griffin stem)                               #
# --------------------------------------------------------------------------- #


def init_conv1d(key: Array, channels: int, width: int, dtype=jnp.bfloat16) -> Params:
    scale = 1.0 / math.sqrt(width)
    return {
        "w": (jax.random.normal(key, (width, channels), jnp.float32) * scale).astype(dtype),
        "b": jnp.zeros((channels,), dtype),
    }


def causal_conv1d(p: Params, x: Array) -> Array:
    """Depthwise causal conv over sequence.  x: [B, S, C] -> [B, S, C]."""
    width = p["w"].shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):  # width is 4: unrolled taps fuse into one kernel
        out = out + pad[:, i : i + x.shape[1], :].astype(jnp.float32) * p["w"][i].astype(jnp.float32)
    return (out + p["b"].astype(jnp.float32)).astype(x.dtype)


def conv1d_step(p: Params, window: Array, x_t: Array) -> Tuple[Array, Array]:
    """Single decode step.  window: [B, width-1, C] past inputs; returns
    (y_t [B, C], new_window)."""
    width = p["w"].shape[0]
    full = jnp.concatenate([window, x_t[:, None, :]], axis=1)  # [B, width, C]
    y = jnp.einsum("bwc,wc->bc", full.astype(jnp.float32), p["w"].astype(jnp.float32))
    y = (y + p["b"].astype(jnp.float32)).astype(x_t.dtype)
    return y, full[:, 1:, :]
