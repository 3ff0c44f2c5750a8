"""Overlapped collective matmul (compute/communication overlap,
DESIGN.md section 3).

``ag_matmul``: computes ``all_gather(x) @ w`` without ever materializing the
gathered operand: each of the N ring steps multiplies the currently-resident
x-chunk while the next chunk is in flight on a ``ppermute``.  On TPU the
collective-permute DMA runs async to the MXU, hiding (N-1)/N of the
communication behind compute — the standard Wang et al. / Megatron-style
decomposition, expressed in shard_map so XLA sees the explicit ring.

``rs_matmul``: the reverse (matmul + reduce-scatter fused): each step
computes the partial product destined for one shard and ships the running
partial around the ring — communication again hides behind the next step's
matmul.  Together they form the overlapped TP pair
(column-parallel in, row-parallel out).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ag_matmul", "rs_matmul", "make_overlapped_tp_matmuls"]

Array = jax.Array


def ag_matmul(x_local: Array, w_local: Array, axis_name: str) -> Array:
    """Inside shard_map: ``concat_i(x_i) @ w_local`` via a compute/permute ring.

    x_local: [m_loc, k] (this device's row shard of X)
    w_local: [k, n_loc] (this device's column shard of W)
    returns: [m_loc * N, n_loc] (all X rows against the local W columns)
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m_loc = x_local.shape[0]
    out = jnp.zeros((n * m_loc, w_local.shape[1]), x_local.dtype)
    perm = [(j, (j + 1) % n) for j in range(n)]
    chunk = x_local
    src = idx
    for _ in range(n):
        # the matmul of the resident chunk overlaps the in-flight ppermute
        piece = jnp.dot(chunk, w_local, preferred_element_type=jnp.float32)
        out = jax.lax.dynamic_update_slice(
            out, piece.astype(out.dtype), (src * m_loc, 0)
        )
        chunk = jax.lax.ppermute(chunk, axis_name, perm)
        src = (src - 1) % n
    return out


def rs_matmul(x_local: Array, w_local: Array, axis_name: str) -> Array:
    """Inside shard_map: ``reduce_scatter(x_full_rows @ w_local, rows)``.

    x_local: [m, k_loc] (full rows, K sharded)  w_local: [k_loc, n]
    returns: [m / N, n]  (this device's row shard of the summed product)

    Ring schedule: at each step, add the partial for the shard the running
    buffer is about to visit, then permute the buffer.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = x_local.shape[0]
    m_loc = m // n
    perm = [(j, (j + 1) % n) for j in range(n)]
    acc = jnp.zeros((m_loc, w_local.shape[1]), jnp.float32)
    for i in range(n):
        # which output shard does this step contribute to?  The buffer ends
        # at device d after the remaining (n-1-i) hops: target = idx + n-1-i
        tgt = (idx + (n - 1 - i)) % n
        rows = jax.lax.dynamic_slice(
            x_local, (tgt * m_loc, 0), (m_loc, x_local.shape[1])
        )
        acc = acc + jnp.dot(rows, w_local, preferred_element_type=jnp.float32)
        if i != n - 1:
            acc = jax.lax.ppermute(acc, axis_name, perm)
    return acc.astype(x_local.dtype)


def make_overlapped_tp_matmuls(mesh: Mesh, axis_name: str = "model"):
    """shard_map-wrapped pair for testing / drop-in TP layers.

    ag(x [M, K] sharded P(axis, None), w [K, N] sharded P(None, axis))
        -> y [M, N] sharded P(None, axis)
    rs(x [M, K] sharded P(None, axis), w [K, N] sharded P(axis, None))
        -> y [M, N] sharded P(axis, None)
    """

    ag = shard_map(
        lambda x, w: ag_matmul(x, w, axis_name),
        mesh=mesh,
        in_specs=(P(axis_name, None), P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False,
    )
    rs = shard_map(
        lambda x, w: rs_matmul(x, w, axis_name),
        mesh=mesh,
        in_specs=(P(None, axis_name), P(axis_name, None)),
        out_specs=P(axis_name, None),
        check_vma=False,
    )
    return ag, rs
