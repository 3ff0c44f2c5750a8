"""Plain reference of ``style_transfer_512``: the fast-style-transfer
generator in straightforward ``jax.numpy`` (no kernels, no batching tricks,
no graph passes), and the seeded, column-pruned weights both sides get.

Layers, with ``c = base_channels``: stem conv (``stem_kernel``) to c,
instance norm, relu; two stride-2 3x3 convs to 2c and 4c, each with
instance norm and relu; ``residual_blocks`` blocks of [1x1 conv, instance
norm, relu, 3x3 conv, instance norm] added to their input; two stages of
nearest 2x upsampling, 3x3 conv to half the channels, instance norm, relu;
an output conv (``stem_kernel``) to 3 channels.  Every conv has a bias and
zero "SAME" padding; instance norm is biased-variance over H x W with
``norm_eps``.

Names of the parameters follow the layer names below, one dict per layer:
``{"w": [out, in, k, k], "b": [out]}`` for a conv, ``{"scale", "bias"}``
for an instance norm.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Dict[str, jax.Array]]


def convs(cfg: Dict[str, Any]) -> List[Tuple[str, int, int, int, int, int]]:
    """``(name, c_in, c_out, kernel, stride, upsample_before)`` of every conv
    in execution order."""
    c, k0 = int(cfg["base_channels"]), int(cfg["stem_kernel"])
    out = [("conv_in", int(cfg["frame"][0]), c, k0, 1, 1)]
    for i in range(2):
        out.append((f"down{i}", c, 2 * c, int(cfg["body_kernel"]), 2, 1))
        c *= 2
    for i in range(int(cfg["residual_blocks"])):
        out.append((f"res{i}_c1", c, c, int(cfg["residual_entry_kernel"]), 1, 1))
        out.append((f"res{i}_c2", c, c, int(cfg["body_kernel"]), 1, 1))
    for i in range(2):
        out.append((f"up{i}", c, c // 2, int(cfg["body_kernel"]), 1, 2))
        c //= 2
    out.append(("conv_out", c, int(cfg["frame"][0]), k0, 1, 1))
    return out


def norms(cfg: Dict[str, Any]) -> List[Tuple[str, int]]:
    """``(name, channels)`` of every instance norm."""
    c = int(cfg["base_channels"])
    out = [("in_in", c)]
    for i in range(2):
        c *= 2
        out.append((f"down{i}_in", c))
    for i in range(int(cfg["residual_blocks"])):
        out += [(f"res{i}_n1", c), (f"res{i}_n2", c)]
    for i in range(2):
        c //= 2
        out.append((f"up{i}_in", c))
    return out


def kept_channels(cfg: Dict[str, Any], c_in: int) -> int:
    """Input channels a conv keeps under the config's column pruning; the
    image-input conv keeps all."""
    if c_in <= int(cfg["frame"][0]):
        return c_in
    return c_in - int(round(c_in * float(cfg["pruning"]["sparsity"])))


def init(key: jax.Array, cfg: Dict[str, Any]) -> Params:
    """Seeded weights, column-pruned: in every conv but the image-input one,
    the input channels of least summed squared weight are zeroed.  Jit this
    to make them on the device in one call."""
    p: Params = {}
    layers = convs(cfg)
    keys = jax.random.split(key, 2 * len(layers) + 2 * len(norms(cfg)))
    for i, (name, ci, co, k, _, _) in enumerate(layers):
        w = jax.random.normal(keys[2 * i], (co, ci, k, k), jnp.float32) / math.sqrt(ci * k * k)
        keep = kept_channels(cfg, ci)
        if keep < ci:
            energy = jnp.sum(w * w, axis=(0, 2, 3))
            cut = jnp.sort(energy)[ci - keep]
            w = w * (energy >= cut).astype(w.dtype)[None, :, None, None]
        b = 0.1 * jax.random.normal(keys[2 * i + 1], (co,), jnp.float32)
        p[name] = {"w": w, "b": b}
    base = 2 * len(layers)
    for j, (name, c) in enumerate(norms(cfg)):
        ks = keys[base + 2 * j], keys[base + 2 * j + 1]
        p[name] = {
            "scale": 1.0 + 0.1 * jax.random.normal(ks[0], (c,), jnp.float32),
            "bias": 0.1 * jax.random.normal(ks[1], (c,), jnp.float32),
        }
    return p


def _conv(x, p, stride, round_operand):
    w = p["w"].astype(x.dtype)
    y = jax.lax.conv_general_dilated(
        round_operand(x), round_operand(w), (stride, stride), "SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return y + p["b"].astype(x.dtype)[None, :, None, None]


def _inorm(x, p, eps):
    mu = x.mean(axis=(2, 3), keepdims=True)
    var = x.var(axis=(2, 3), keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * p["scale"].astype(x.dtype)[None, :, None, None] + p["bias"].astype(x.dtype)[None, :, None, None]


def forward(params: Params, x: jax.Array, cfg: Dict[str, Any], *,
            dtype=jnp.float32, operand_dtype=None) -> jax.Array:
    """``[N, 3, H, W] -> [N, 3, H, W]``.  ``dtype`` is the precision every
    activation is held in; ``operand_dtype``, when given, rounds both
    operands of every conv to it first (a lower-precision contraction).
    Call under ``jax.default_matmul_precision("highest")`` for the float32
    reference."""
    eps = float(cfg["norm_eps"])
    rnd = (lambda a: a) if operand_dtype is None else (
        lambda a: a.astype(operand_dtype).astype(dtype))
    layers = {name: (stride, up) for name, _, _, _, stride, up in convs(cfg)}

    def conv(h, name):
        stride, up = layers[name]
        if up > 1:
            h = jnp.repeat(jnp.repeat(h, up, axis=2), up, axis=3)
        return _conv(h, params[name], stride, rnd)

    h = x.astype(dtype)
    h = jax.nn.relu(_inorm(conv(h, "conv_in"), params["in_in"], eps))
    for i in range(2):
        h = jax.nn.relu(_inorm(conv(h, f"down{i}"), params[f"down{i}_in"], eps))
    for i in range(int(cfg["residual_blocks"])):
        r = jax.nn.relu(_inorm(conv(h, f"res{i}_c1"), params[f"res{i}_n1"], eps))
        r = _inorm(conv(r, f"res{i}_c2"), params[f"res{i}_n2"], eps)
        h = h + r
    for i in range(2):
        h = jax.nn.relu(_inorm(conv(h, f"up{i}"), params[f"up{i}_in"], eps))
    return conv(h, "conv_out").astype(jnp.float32)
