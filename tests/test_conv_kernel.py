"""Implicit-GEMM Pallas conv2d: kernel parity across shapes/strides/padding,
the three lowering schemes (dense f32, channel-pruned, INT8 W8/W8A8),
in-tile epilogue programs, the lax.conv fallback matrix, the conv tuning-key
family, and the executor/app acceptance gates (every demo-app conv lowers
through the kernel, zero fallbacks, plan steps at or below the PR 2
baseline).  Everything runs in interpret mode (CPU container)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import (
    GraphBuilder,
    compile_plan,
    optimize,
    registered_ops,
)
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.models.cnn import APPS, app_masks
from repro.quant import QTensor

KEY = jax.random.PRNGKey(0)

APP_INPUTS = {
    "style_transfer": (1, 3, 16, 16),
    "coloring": (1, 1, 16, 16),
    "super_resolution": (1, 3, 8, 8),
}

#: PR 2's plan-step acceptance baseline (33/30/37); folding the channel
#: compaction into the conv nodes cut these further
STEP_CAPS = {"style_transfer": 33, "coloring": 30, "super_resolution": 37}


def _conv_case(n, c, h, w, o, k, key=KEY):
    x = jax.random.normal(key, (n, c, h, w))
    wt = jax.random.normal(jax.random.PRNGKey(1), (o, c, k, k)) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(2), (o,)) * 0.1
    return x, wt, b


# --------------------------------------------------------------------------- #
# dense f32 parity                                                             #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
@pytest.mark.parametrize(
    "shape",
    [
        (1, 3, 7, 9, 5, 3),    # odd spatial dims, 3x3
        (2, 5, 11, 13, 7, 3),  # odd everything, batch 2
        (1, 4, 8, 8, 6, 1),    # 1x1 filter
        (1, 2, 16, 10, 3, 3),
    ],
)
def test_conv_kernel_parity(shape, stride, padding):
    n, c, h, w, o, k = shape
    x, wt, b = _conv_case(n, c, h, w, o, k)
    got = kops.conv2d(x, wt, b, stride=stride, padding=padding, activation="relu")
    want = ref.conv2d_ref(x, wt, b, stride=stride, padding=padding, activation="relu")
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_conv_kernel_no_bias_no_activation():
    x, wt, _ = _conv_case(1, 3, 9, 9, 4, 3)
    got = kops.conv2d(x, wt)
    want = ref.conv2d_ref(x, wt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_conv_kernel_explicit_pad_pairs():
    """lax-style ((ph_lo, ph_hi), (pw_lo, pw_hi)) padding lowers through the
    kernel (asymmetric pads included); negative (cropping) pads fall back."""
    x, wt, b = _conv_case(1, 3, 8, 9, 4, 3)
    kops.reset_conv_fallbacks()
    pads = ((1, 0), (2, 1))
    got = kops.conv2d(x, wt, b, stride=2, padding=pads)
    assert kops.conv_fallback_counts() == {}
    want = ref.conv2d_ref(x, wt, b, stride=2, padding=pads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    neg = ((-1, 0), (0, 0))
    got_n = kops.conv2d(x, wt, b, padding=neg)
    assert kops.conv_fallback_counts() == {"padding": 1}
    want_n = ref.conv2d_ref(x, wt, b, padding=neg)
    np.testing.assert_allclose(np.asarray(got_n), np.asarray(want_n), rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------- #
# channel-pruned scheme                                                        #
# --------------------------------------------------------------------------- #


def test_conv_kernel_channel_pruned_contracts_kept_only():
    x = jax.random.normal(KEY, (2, 10, 9, 9))
    kept = jnp.asarray([0, 3, 4, 7, 9], jnp.int32)
    wt = jax.random.normal(jax.random.PRNGKey(1), (8, 5, 3, 3)) * 0.1
    got = kops.conv2d(x, wt, None, kept=kept, stride=2)
    want = ref.conv2d_ref(jnp.take(x, kept, axis=1), wt, None, stride=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_conv_kernel_empty_pruned_channel_set_is_pure_epilogue():
    """All input channels pruned: the empty contraction contributes zeros,
    so the output is bias + activation + epilogue only."""
    x = jax.random.normal(KEY, (2, 6, 8, 8))
    wt = jnp.zeros((4, 0, 3, 3))
    kept = jnp.zeros((0,), jnp.int32)
    b = jnp.asarray([1.0, -2.0, 0.5, 3.0])
    got = kops.conv2d(x, wt, b, kept=kept, activation="relu")
    want = jnp.broadcast_to(jax.nn.relu(b)[None, :, None, None], (2, 4, 8, 8))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------- #
# INT8 schemes                                                                 #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("scheme", ["w8", "w8a8"])
@pytest.mark.parametrize("stride", [1, 2])
def test_conv_kernel_int8_matches_oracle(scheme, stride):
    x, wt, b = _conv_case(1, 6, 12, 12, 8, 3)
    qt = QTensor.from_float(wt, axis=0)
    xs = float(jnp.max(jnp.abs(x))) / 127.0 if scheme == "w8a8" else None
    got = kops.conv2d(
        x, qt.values, b, w_scale=qt.scale, x_scale=xs, stride=stride,
        activation="relu",
    )
    want = ref.qconv2d_ref(
        x, qt.values, qt.scale, b, x_scale=xs, stride=stride, activation="relu"
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    # and the whole scheme stays close to fp32
    f32 = ref.conv2d_ref(x, wt, b, stride=stride, activation="relu", out_dtype=jnp.float32)
    assert float(jnp.abs(got - f32).max()) <= 5e-2


def test_conv_kernel_int8_requires_scale():
    x, wt, _ = _conv_case(1, 4, 8, 8, 4, 3)
    qt = QTensor.from_float(wt, axis=0)
    with pytest.raises(ValueError, match="w_scale"):
        kops.conv2d(x, qt.values)
    with pytest.raises(ValueError, match="int8"):
        kops.conv2d(x, wt, x_scale=0.1)


# --------------------------------------------------------------------------- #
# in-tile epilogue programs                                                    #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("scheme", ["f32", "w8"])
def test_conv_kernel_epilogue_program_in_tile(scheme):
    x, wt, b = _conv_case(2, 4, 9, 9, 6, 3)
    side = jax.random.normal(jax.random.PRNGKey(3), (2, 6, 9, 9))
    steps = (("add", 0), ("activation", "gelu"), ("mul", 0))
    if scheme == "w8":
        qt = QTensor.from_float(wt, axis=0)
        got = kops.conv2d(
            x, qt.values, b, w_scale=qt.scale,
            epilogue=steps, epilogue_sides=(side,),
        )
        base = ref.qconv2d_ref(x, qt.values, qt.scale, b)
    else:
        got = kops.conv2d(x, wt, b, epilogue=steps, epilogue_sides=(side,))
        base = ref.conv2d_ref(x, wt, b, out_dtype=jnp.float32)
    want = ref.apply_steps_ref(base, steps, [side])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_conv_kernel_epilogue_validation():
    x, wt, _ = _conv_case(1, 3, 8, 8, 4, 3)
    with pytest.raises(ValueError, match="slot"):
        kops.conv2d(x, wt, epilogue=(("add", 0),), epilogue_sides=())


# --------------------------------------------------------------------------- #
# fallback matrix                                                              #
# --------------------------------------------------------------------------- #


def test_conv_fallback_groups_and_dilation_counted_and_exact():
    x = jax.random.normal(KEY, (1, 4, 8, 8))
    wg = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 3, 3)) * 0.1
    wd = jax.random.normal(jax.random.PRNGKey(2), (4, 4, 3, 3)) * 0.1
    kops.reset_conv_fallbacks()
    got_g = kops.conv2d(x, wg, None, groups=2)
    got_d = kops.conv2d(x, wd, None, dilation=2)
    assert kops.conv_fallback_counts() == {"groups": 1, "dilation": 1}
    np.testing.assert_allclose(
        np.asarray(got_g), np.asarray(ref.conv2d_ref(x, wg, None, groups=2)),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(ref.conv2d_ref(x, wd, None, dilation=2)),
        rtol=1e-4, atol=1e-5,
    )


def test_conv_fallback_preserves_epilogue_and_int8():
    """A fallback must be an engine change, never a semantics change: the
    int8 + epilogue math matches the oracle exactly."""
    x, wt, b = _conv_case(1, 4, 8, 8, 4, 3)
    qt = QTensor.from_float(wt, axis=0)
    side = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 8, 8))
    steps = (("add", 0), ("activation", "tanh"))
    kops.reset_conv_fallbacks()
    got = kops.conv2d(
        x, qt.values, b, w_scale=qt.scale, dilation=2,
        epilogue=steps, epilogue_sides=(side,),
    )
    assert kops.conv_fallback_counts() == {"dilation": 1}
    want = ref.apply_steps_ref(
        ref.qconv2d_ref(x, qt.values, qt.scale, b, dilation=2), steps, [side]
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------- #
# tuning-key family                                                            #
# --------------------------------------------------------------------------- #


def test_conv_tuning_key_family_never_collides():
    cache = kops.tuning_cache()
    prev = dict(cache.entries)
    try:
        x, wt, _ = _conv_case(1, 6, 8, 8, 4, 3)
        qt = QTensor.from_float(wt, axis=0)
        kept = jnp.asarray([0, 2, 3], jnp.int32)
        kops.conv2d(x, wt)
        kops.conv2d(x, wt[:, :3], kept=kept)
        kops.conv2d(x, qt.values, w_scale=qt.scale)
        kops.conv2d(x, qt.values, w_scale=qt.scale, x_scale=0.02)
        shape8 = (1, 6, 8, 8, 4, 3, 3, 1)
        k_f32 = kops.TuningCache.key_nd("conv2d", shape8, jnp.float32, "dense+f32", True)
        k_chan = kops.TuningCache.key_nd(
            "conv2d", (1, 3, 8, 8, 4, 3, 3, 1), jnp.float32, "channelcompact+f32", True
        )
        k_w8 = kops.TuningCache.key_nd("conv2d", shape8, jnp.float32, "dense+w8", True)
        k_a8 = kops.TuningCache.key_nd("conv2d", shape8, jnp.int8, "dense+w8a8", True)
        # same dims, different output geometry: VALID suffixes the fmt so it
        # never shares a winner with SAME
        kops.conv2d(x, wt, padding="VALID")
        k_valid = kops.TuningCache.key_nd(
            "conv2d", shape8, jnp.float32, "dense+f32+valid", True
        )
        keys = {k_f32, k_chan, k_w8, k_a8, k_valid}
        assert len(keys) == 5  # schemes/formats/paddings never alias
        for k in keys:
            assert k in cache.entries, k
        # the conv shape signature carries all eight dims
        assert k_f32.split("|")[1] == "1x6x8x8x4x3x3x1"
    finally:
        cache.entries = prev


def test_conv_epilogue_keys_separately():
    cache = kops.tuning_cache()
    prev = dict(cache.entries)
    try:
        x, wt, _ = _conv_case(1, 4, 8, 8, 4, 3)
        side = jnp.zeros((1, 4, 8, 8))
        kops.conv2d(x, wt, epilogue=(("add", 0),), epilogue_sides=(side,))
        k = kops.TuningCache.key_nd(
            "conv2d", (1, 4, 8, 8, 4, 3, 3, 1), jnp.float32, "dense+f32+e1s1", True
        )
        assert k in cache.entries
    finally:
        cache.entries = prev


# --------------------------------------------------------------------------- #
# executor integration                                                         #
# --------------------------------------------------------------------------- #


def _conv_graph(c=6, o=8, k=3, with_norm=False):
    b = GraphBuilder(["x"])
    wt = jax.random.normal(KEY, (o, c, k, k)) * 0.1
    h = b.add("conv2d", "x", name="c1",
              params={"w": wt, "b": jnp.zeros((o,))}, stride=1, padding="SAME")
    if with_norm:
        h = b.add("norm", h, name="in1",
                  params={"scale": jnp.ones((o,)), "bias": jnp.zeros((o,))},
                  kind="instance")
    h = b.add("activation", h, name="a1", fn="relu")
    return b.build(h)


def test_kernel_backend_conv_epilogue_runs_in_tile():
    g = optimize(_conv_graph())
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 11, 11))
    got = compile_plan(g, backend="kernel")(g.params, x)
    want = compile_plan(g, backend="reference")(g.params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_kernel_backend_conv_norm_epilogue_falls_back_to_jnp_tail():
    """Instance-norm steps need whole spatial planes: the kernel runs the
    GEMM, the norm runs as a jnp tail -- still one plan step, exact parity."""
    g = optimize(_conv_graph(with_norm=True))
    (node,) = [n for n in g.nodes if n.op == "conv2d"]
    assert any(s[0] == "norm_instance" for s in node.attrs["epilogue"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 9, 9))
    got = compile_plan(g, backend="kernel")(g.params, x)
    want = compile_plan(g, backend="reference")(g.params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_substitute_sparse_folds_channel_compaction_into_conv():
    """Dead input channels fold into the conv node (format=channelcompact +
    kept param) -- no gather glue node, one fewer plan step."""
    from repro.core.pruning import Column

    g = _conv_graph(c=8)
    w = g.params["c1"]["w"]
    mask = jnp.ones_like(w).at[:, ::2].set(0.0)  # kill half the input channels
    go = optimize(g, {"c1": mask}, {"c1": Column(0.5)})
    (conv,) = [n for n in go.nodes if n.op == "conv2d"]
    assert conv.attrs["format"] == "channelcompact"
    assert go.params[conv.name]["w"].shape[1] == 4
    assert go.params[conv.name]["kept"].shape == (4,)
    assert not any(n.op == "gather_channels" for n in go.nodes)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 9, 9))
    for backend in ("kernel", "reference"):
        got = compile_plan(go, backend=backend)(go.params, x)
        want = compile_plan(g, backend="reference")(
            {**g.params, "c1": {**g.params["c1"], "w": w * mask}}, x
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_qconv2d_is_quant_backend_only():
    assert "qconv2d" in registered_ops("quant")
    assert "qconv2d" not in registered_ops("kernel")


def test_memory_estimate_reports_conv_vmem_workspace():
    g = optimize(_conv_graph())
    plan = compile_plan(g, backend="reference")
    mem = plan.memory_estimate(jax.ShapeDtypeStruct((1, 6, 16, 16), jnp.float32))
    assert mem["peak_vmem_workspace_bytes"] > 0
    (conv_name,) = [s.node.name for s in plan.steps if s.node.op == "conv2d"]
    ws = mem["vmem_workspace_by_step"][conv_name]
    # at least the resident image + one im2col patch tile
    assert ws >= 16 * 16 * 6 * 4


# --------------------------------------------------------------------------- #
# the launch.tune pre-warm CLI                                                 #
# --------------------------------------------------------------------------- #


def test_launch_tune_smoke_prewarms_and_saves_cache(tmp_path, monkeypatch):
    """--smoke sweeps every key reachable from a demo app's plan on CPU and
    persists a loadable cache JSON (the CI-sized slice of the ROADMAP's
    hardware tuning sweeps)."""
    from repro.launch import tune

    cache = kops.tuning_cache()
    prev_enabled, prev_entries = cache.enabled, dict(cache.entries)
    out = tmp_path / "tuned.json"
    monkeypatch.setattr(
        "sys.argv",
        ["tune", "--graph-app", "coloring", "--smoke", "--size", "8",
         "--out", str(out)],
    )
    # the CLI turns on the persistent compile cache; a test never does
    monkeypatch.setattr(tune, "enable_compile_cache", lambda: None)
    try:
        tune.main()
        assert out.exists()
        fresh = kops.TuningCache(enabled=False)
        fresh.load(str(out))
        swept_ops = {k.split("|")[0] for k in fresh.entries}
        assert "conv2d" in swept_ops and "matmul" in swept_ops
        assert all(e.source == "loaded" for e in fresh.entries.values())
    finally:
        cache.enabled, cache.entries = prev_enabled, prev_entries


# --------------------------------------------------------------------------- #
# app acceptance: every demo-app conv lowers through the Pallas kernel         #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("app", list(APPS))
def test_app_kernel_plans_lower_all_convs_through_pallas(app):
    g = APPS[app](KEY, base=8)
    masks, structures = app_masks(g, app, sparsity=0.5)
    go = optimize(g, masks, structures)
    plan_k = compile_plan(go, backend="kernel")
    assert len(plan_k.steps) <= STEP_CAPS[app], (len(plan_k.steps), STEP_CAPS[app])
    x = jax.random.normal(jax.random.PRNGKey(1), APP_INPUTS[app])
    kops.reset_conv_fallbacks()
    got = plan_k(go.params, x)  # eager: the fallback counter sees every call
    assert kops.conv_fallback_counts() == {}, kops.conv_fallback_counts()
    want = compile_plan(go, backend="reference")(go.params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# PR 6: tiled-K contraction + 1x1 direct-GEMM fast path                        #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("block_c", [0, 2, 4])
def test_conv_tiled_k_matches_resident_and_oracle(block_c):
    """Pinning block_c > 0 streams K in channel slabs through the cross-step
    accumulator; the result is at tolerance with both the resident full-K
    path (block_c=0) and the lax oracle."""
    x, wt, b = _conv_case(2, 6, 11, 13, 8, 3)
    got = kops.conv2d(x, wt, b, activation="relu",
                      block_h=8, block_o=128, block_c=block_c)
    want = ref.conv2d_ref(x, wt, b, activation="relu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("scheme", ["w8", "w8a8"])
def test_conv_tiled_k_int8_schemes_match_oracle(scheme):
    """block_c slabs accumulate in int32 for w8a8 (channel zero-padding
    contributes exact zeros) and f32 for w8-dequant."""
    x, wt, b = _conv_case(1, 6, 10, 10, 8, 3)
    qt = QTensor.from_float(wt, axis=0)
    xs = float(jnp.max(jnp.abs(x))) / 127.0 if scheme == "w8a8" else None
    got = kops.conv2d(x, qt.values, b, w_scale=qt.scale, x_scale=xs,
                      block_h=8, block_o=128, block_c=2)
    want = ref.qconv2d_ref(x, qt.values, qt.scale, b, x_scale=xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_conv_tiled_k_epilogue_runs_on_last_step_only():
    """The epilogue (bias/activation/steps) must fire exactly once, on the
    final K step, over the accumulated sum -- not per slab."""
    x, wt, b = _conv_case(1, 4, 9, 9, 6, 3)
    side = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 9, 9))
    steps = (("add", 0), ("activation", "gelu"))
    got = kops.conv2d(x, wt, b, epilogue=steps, epilogue_sides=(side,),
                      block_h=8, block_o=128, block_c=2)
    want = ref.apply_steps_ref(
        ref.conv2d_ref(x, wt, b, out_dtype=jnp.float32), steps, [side]
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_wide_channel_conv_no_longer_vmem_fallback():
    """PR 4's guard rejected any shape whose resident full-K workspace
    overflowed VMEM; with tiled-K the guard passes whenever SOME block_c
    candidate fits, so the wide-channel config lowers through Pallas."""
    c, h, w, kh = 2048, 64, 64, 3
    # the resident workspace genuinely overflows (the old fallback trigger)
    resident = kops.conv_vmem_workspace(c, h, w, kh, kh, 1, "SAME", 8, 128)
    assert resident["total"] > kops._CONV_VMEM_LIMIT
    # ... but a tiled block_c candidate fits, so the hw guard passes now
    assert kops.conv_fallback_reason(c, h, w, kh, kh, 1, "SAME", interpret=False) is None
    # and the hw default resolution elects a tiled block_c for this shape
    dh, do_, bc = kops._conv_default_blocks(c, h, w, kh, kh, 1, "SAME", 4, 4, False)
    assert bc > 0
    tiled = kops.conv_vmem_workspace(c, h, w, kh, kh, 1, "SAME", dh, do_, bc)
    assert tiled["total"] <= kops._CONV_VMEM_LIMIT
    # pinning a still-too-big block_c is honored verbatim -> fallback
    assert kops.conv_fallback_reason(
        c, h, w, kh, kh, 1, "SAME", interpret=False, block_c=0
    ) == "vmem"


def test_wide_channel_conv_runs_through_pallas_at_parity():
    """A (scaled-down) wide-channel config executes the tiled-K kernel path
    end to end: zero fallbacks, oracle parity."""
    x, wt, b = _conv_case(1, 64, 8, 8, 8, 3)
    kops.reset_conv_fallbacks()
    got = kops.conv2d(x, wt, b, block_h=8, block_o=128, block_c=16)
    assert kops.conv_fallback_counts() == {}
    want = ref.conv2d_ref(x, wt, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_conv_1x1_fast_path_elected_counted_and_parity():
    """Unit-tap convs bypass im2col and lower to the dense/quant GEMM
    kernels; elections are counted per scheme like fallbacks."""
    x = jax.random.normal(KEY, (2, 6, 12, 12))
    w1 = jax.random.normal(jax.random.PRNGKey(1), (8, 6, 1, 1)) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(2), (8,)) * 0.1
    kops.reset_conv_fastpaths()
    kops.reset_conv_fallbacks()
    got = kops.conv2d(x, w1, b, activation="relu")
    assert kops.conv_fastpath_counts() == {"f32": 1}
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.conv2d_ref(x, w1, b, activation="relu")),
        rtol=1e-4, atol=1e-5,
    )
    # stride subsamples spatially before the GEMM
    got_s = kops.conv2d(x, w1, b, stride=2)
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(ref.conv2d_ref(x, w1, b, stride=2)),
        rtol=1e-4, atol=1e-5,
    )
    # int8 schemes route to qmatmul and count under their scheme
    qt = QTensor.from_float(w1, axis=0)
    got_q = kops.conv2d(x, qt.values, b, w_scale=qt.scale, x_scale=0.05)
    assert kops.conv_fastpath_counts()["w8a8"] == 1
    np.testing.assert_allclose(
        np.asarray(got_q),
        np.asarray(ref.qconv2d_ref(x, qt.values, qt.scale, b, x_scale=0.05)),
        rtol=1e-4, atol=1e-5,
    )
    # channel compaction gathers kept channels before the reshape
    kept = jnp.asarray([0, 2, 5], jnp.int32)
    got_k = kops.conv2d(x, w1[:, :3], b, kept=kept)
    assert kops.conv_fastpath_counts()["f32"] >= 3
    np.testing.assert_allclose(
        np.asarray(got_k),
        np.asarray(ref.conv2d_ref(jnp.take(x, kept, axis=1), w1[:, :3], b)),
        rtol=1e-4, atol=1e-5,
    )
    assert kops.conv_fallback_counts() == {}  # elections are not fallbacks


def test_conv_1x1_election_rules():
    """Election requires unit taps, groups=1, no effective padding, live
    input channels; pinned block sizes or gemm_1x1=False bypass it so the
    im2col kernel stays testable on 1x1 shapes."""
    assert kops.conv_gemm1x1_elected(1, 1, 1, "SAME", 6)
    assert kops.conv_gemm1x1_elected(1, 1, 1, "VALID", 6)
    assert kops.conv_gemm1x1_elected(1, 1, 1, ((0, 0), (0, 0)), 6)
    assert not kops.conv_gemm1x1_elected(3, 3, 1, "SAME", 6)   # taps
    assert not kops.conv_gemm1x1_elected(1, 1, 2, "SAME", 6)   # groups
    assert not kops.conv_gemm1x1_elected(1, 1, 1, ((1, 0), (0, 0)), 6)  # pad
    assert not kops.conv_gemm1x1_elected(1, 1, 1, "SAME", 0)   # no live K
    x = jax.random.normal(KEY, (1, 4, 8, 8))
    w1 = jax.random.normal(jax.random.PRNGKey(1), (6, 4, 1, 1)) * 0.1
    kops.reset_conv_fastpaths()
    kops.conv2d(x, w1, block_h=8, block_o=128)  # pinned -> im2col kernel
    kops.conv2d(x, w1, gemm_1x1=False)
    assert kops.conv_fastpath_counts() == {}


@pytest.mark.parametrize("app", list(APPS))
def test_app_1x1_convs_lower_through_fast_path(app):
    """Every demo app carries at least one 1x1 conv (style/SR residual
    blocks are bottleneck/WDSR-B style; coloring's fusion conv): each app's
    kernel plan elects the direct-GEMM fast path with zero fallbacks."""
    g = APPS[app](KEY, base=8)
    n_1x1 = sum(
        1 for n in g.nodes
        if n.op == "conv2d" and g.params[n.name]["w"].shape[2] == 1
    )
    assert n_1x1 >= 1, app
    masks, structures = app_masks(g, app, sparsity=0.5)
    go = optimize(g, masks, structures)
    plan_k = compile_plan(go, backend="kernel")
    x = jax.random.normal(jax.random.PRNGKey(1), APP_INPUTS[app])
    kops.reset_conv_fastpaths()
    kops.reset_conv_fallbacks()
    got = plan_k(go.params, x)  # eager: counters see every call
    fastpaths = kops.conv_fastpath_counts()
    assert sum(fastpaths.values()) >= n_1x1, (app, fastpaths)
    assert kops.conv_fallback_counts() == {}, kops.conv_fallback_counts()
    want = compile_plan(go, backend="reference")(go.params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
