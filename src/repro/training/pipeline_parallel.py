"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

The model's layer stack is cut into P contiguous stages; M microbatches
stream through a (M + P - 1)-tick schedule.  Stage handoff is a
``jax.lax.ppermute`` (differentiable -- the backward pass ppermutes the
cotangents the other way, giving the 1F1B-equivalent reverse schedule for
free under ``jax.grad``).

This is the documented alternative for the cross-pod axis when DCN bandwidth
makes pure DP gradient sync the binding constraint (DESIGN.md section 5); the
assigned production mesh keeps ``pod`` as DP, so pipeline runs are opt-in
(``launch/train.py --pipeline``).

Shapes inside shard_map (per stage device):
  params_stacked: [Lp, ...]    (Lp = layers per stage)
  x:              [M, mb, ...] (all microbatches resident; simple GPipe)
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_forward", "make_pipelined_loss"]

PyTree = Any
Array = jax.Array


def _stage_scan(layer_fn, stage_params, x):
    """Apply this stage's Lp layers sequentially to x."""

    def body(h, lp):
        return layer_fn(lp, h), None

    out, _ = jax.lax.scan(body, x, stage_params)
    return out


def pipeline_forward(
    layer_fn: Callable[[PyTree, Array], Array],
    params_stacked: PyTree,  # [L, ...] leaves, L = P * Lp
    x_micro: Array,  # [M, mb, ...]
    *,
    mesh: Mesh,
    axis_name: str = "pipe",
) -> Array:
    """Run the pipeline; returns outputs [M, mb, ...] (valid on all stages).

    GPipe schedule: at tick t, the stage holds microbatch (t - stage_id) if
    0 <= t - stage_id < M.  After the loop the final activations have exited
    the last stage; we ppermute them back to all stages via all_gather of the
    last stage's buffer.
    """
    n_stages = mesh.shape[axis_name]
    m = x_micro.shape[0]
    n_ticks = m + n_stages - 1

    def body(stage_params, xm):
        stage = jax.lax.axis_index(axis_name)
        mb_shape = xm.shape[1:]
        outputs = jnp.zeros_like(xm)
        carry = jnp.zeros(mb_shape, xm.dtype)  # incoming activation buffer

        def tick(t, state):
            carry, outputs = state
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < m)
            # stage 0 reads its own microbatch; later stages read the carry
            inp = jnp.where(
                stage == 0,
                jax.lax.dynamic_index_in_dim(xm, jnp.clip(t, 0, m - 1), keepdims=False),
                carry,
            )
            out = _stage_scan(layer_fn, stage_params, inp)
            out = jnp.where(active, out, carry)
            # record finished microbatch on the last stage
            outputs = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.clip(mb_idx, 0, m - 1), axis=0
                ),
                lambda o: o,
                outputs,
            )
            # hand off to the next stage (ring; last->first slot unused)
            nxt = jax.lax.ppermute(
                out, axis_name, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return nxt, outputs

        carry, outputs = jax.lax.fori_loop(0, n_ticks, tick, (carry, outputs))
        # broadcast the last stage's outputs to every stage (psum of one-hot)
        is_last = (stage == n_stages - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * is_last, axis_name)
        return outputs

    # params: layer dim sharded over pipe; x replicated
    p_specs = jax.tree.map(lambda _: P(axis_name), params_stacked)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(p_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(params_stacked, x_micro)


def make_pipelined_loss(
    layer_fn: Callable[[PyTree, Array], Array],
    head_fn: Callable[[Array, Array], Array],  # (activations, labels) -> loss
    *,
    mesh: Mesh,
    axis_name: str = "pipe",
):
    """loss(params_stacked, x_micro, labels_micro) -> scalar (differentiable)."""

    def loss(params_stacked, x_micro, labels_micro):
        out = pipeline_forward(
            layer_fn, params_stacked, x_micro, mesh=mesh, axis_name=axis_name
        )
        return head_fn(out, labels_micro)

    return loss
