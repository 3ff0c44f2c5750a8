"""Gradient compression for the data-parallel all-reduce (distributed-
optimization trick, DESIGN.md section 3).

``int8 + error feedback``: each DP worker quantizes its local gradient to
int8 with a per-tensor f32 scale, the int8 payload is exchanged
(all-gather), dequantized and averaged locally; the quantization residual is
*carried* to the next step (error feedback, Seide et al. 2014 / Karimireddy
et al. 2019) so the compression bias vanishes over time.

Wire accounting vs the baseline fp32 ring all-reduce (2 x N bytes/device):
all-gather moves (d-1)/d x N int8 bytes/device ~= N/4 bytes -> ~8x less
traffic for d >= 8.  Implemented with shard_map so the collective is explicit
in the HLO (visible to the roofline's collective-byte parser).

``topk + error feedback`` (sparsification) is provided as a second policy.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["CompressionConfig", "init_error_feedback", "quantize_int8", "dequantize_int8",
           "compressed_mean_grads", "make_compressed_allreduce"]

PyTree = Any
Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    policy: str = "int8"  # int8 | topk | none
    topk_frac: float = 0.01
    error_feedback: bool = True


def init_error_feedback(grads_template: PyTree) -> PyTree:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_template)


def quantize_int8(x: Array) -> Tuple[Array, Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


def _topk_sparsify(x: Array, frac: float) -> Array:
    flat = x.reshape(-1)
    k = max(1, int(flat.shape[0] * frac))
    thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
    return jnp.where(jnp.abs(x) >= thresh, x, 0.0)


def compressed_mean_grads(
    local_grad: Array,
    err: Array,
    *,
    axis_name: str,
    cfg: CompressionConfig,
) -> Tuple[Array, Array]:
    """Inside shard_map: compress local grad (+error), exchange, average.

    Returns (mean_grad f32, new_error).  Must be called with ``local_grad``
    already *device-local* (shard_map body).
    """
    g = local_grad.astype(jnp.float32)
    if cfg.policy == "none":
        return jax.lax.pmean(g, axis_name), err
    if cfg.error_feedback:
        g = g + err
    if cfg.policy == "topk":
        sent = _topk_sparsify(g, cfg.topk_frac)
        new_err = g - sent
        mean = jax.lax.pmean(sent, axis_name)
        return mean, new_err
    # int8
    q, scale = quantize_int8(g)
    sent = dequantize_int8(q, scale)
    new_err = g - sent
    # exchange the int8 payload: all_gather int8 + local dequant-average.
    qs = jax.lax.all_gather(q, axis_name)  # [d, ...] int8 on the wire
    ss = jax.lax.all_gather(scale, axis_name)  # [d] f32 (negligible)
    mean = jnp.tensordot(ss, qs.astype(jnp.float32), axes=([0], [0])) / qs.shape[0]
    return mean, new_err


def make_compressed_allreduce(
    mesh: Mesh,
    grads_template: PyTree,
    *,
    axis_name: str = "data",
    cfg: CompressionConfig = CompressionConfig(),
) -> Callable[[PyTree, PyTree], Tuple[PyTree, PyTree]]:
    """Builds ``f(per_device_grads, err) -> (mean_grads, err')`` via shard_map.

    ``per_device_grads`` leaves must carry a leading sharded axis of size
    ``mesh.shape[axis_name]`` (one gradient per DP group), i.e. the caller
    computes grads with pjit out-sharded over data and *without* the implicit
    mean -- see examples/train_lm_100m.py for the wiring.
    """

    def body(grads, err):
        return jax.tree.map(
            lambda g, e: compressed_mean_grads(g, e, axis_name=axis_name, cfg=cfg),
            grads,
            err,
        )

    def split_pairs(tree):
        means = jax.tree.map(lambda t: t[0], tree, is_leaf=lambda x: isinstance(x, tuple))
        errs = jax.tree.map(lambda t: t[1], tree, is_leaf=lambda x: isinstance(x, tuple))
        return means, errs

    in_spec = jax.tree.map(lambda _: P(axis_name), grads_template)
    err_spec = jax.tree.map(lambda _: P(axis_name), grads_template)
    out_spec = jax.tree.map(lambda _: (P(), P(axis_name)), grads_template)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(in_spec, err_spec),
        out_specs=out_spec,
        check_vma=False,
    )

    def apply(per_device_grads, err):
        means, errs = split_pairs(fn(per_device_grads, err))
        # body outputs keep the device-local leading axis of length 1
        means = jax.tree.map(lambda m: m[0], means)
        return means, errs

    return apply
