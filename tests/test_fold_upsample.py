"""fold_upsample_conv: a nearest-2x upsample folded into the 3x3 conv after
it (a low-resolution phase conv + pixel shuffle) computes what the pair
computed, and the pass touches nothing else."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import (
    DEFAULT_PIPELINE,
    GraphBuilder,
    PassContext,
    PassManager,
    compile_plan,
    fold_upsample_conv,
)
from repro.models.cnn import APP_ACT_SKIP, APP_QUANT_SKIP, APPS, app_masks
from repro.obs import metrics
from repro.quant import calibrate_plan

KEY = jax.random.PRNGKey(0)
NO_FOLD = tuple(p for p in DEFAULT_PIPELINE if p != "fold_upsample_conv")


def _folds() -> int:
    counts = metrics.registry().label_counts("graph_rewrites_total", "pass")
    return int(counts.get("fold_upsample_conv", 0))


def _app(app, pruned, pipeline=None, **ctx):
    g = APPS[app](KEY, base=8)
    masks, structures = app_masks(g, app, sparsity=0.5) if pruned else ({}, {})
    return PassManager(pipeline).run(
        g, PassContext(masks=masks, structures=structures, **ctx)
    )


def _frames(app):
    c = 1 if app == "coloring" else 3
    return jax.random.normal(jax.random.PRNGKey(1), (2, c, 64, 64))


_EQUIVALENCE = [
    (app, pruned, backend)
    for app in ("style_transfer", "coloring")
    for pruned in (False, True)
    for backend in ("reference", "kernel")
] + [("coloring", True, "w8a8")]


@pytest.mark.parametrize("app,pruned,backend", _EQUIVALENCE)
def test_fold_matches_unfolded_plan(app, pruned, backend):
    x = _frames(app)
    g0 = _app(app, pruned, NO_FOLD)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(compile_plan(g0, backend="reference"))(g0.params, x)
        if backend != "w8a8":
            g1 = _app(app, pruned)
            plan = compile_plan(g1, backend=backend, interpret=True)
            got = jax.jit(plan)(g1.params, x)
            err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
            assert err <= 1e-5, (app, pruned, backend, err)
            return
        # every conv at W8A8, the phase convs quantized per output channel
        # like any other conv: held to the quant backend's parity bound
        g1 = _app(app, pruned)
        table = calibrate_plan(
            compile_plan(g1, backend="reference"), g1.params,
            [jax.random.normal(jax.random.PRNGKey(2 + i), x.shape) for i in range(2)],
        )
        gq = _app(app, pruned, calibration=table, quant_skip=APP_QUANT_SKIP[app],
                  act_quant_skip=APP_ACT_SKIP[app])
        assert all(
            n.attrs.get("scheme") == "w8a8" for n in gq.nodes if n.op == "qconv2d"
        )
        got = jax.jit(compile_plan(gq, backend="quant", interpret=True))(gq.params, x)
        assert float(jnp.abs(got - want).max()) <= 5e-2


@pytest.mark.parametrize("app", ["style_transfer", "coloring"])
def test_apps_fold_both_upsamples(app):
    metrics.registry().reset("graph_rewrites_total")
    ctx = PassContext()
    g = PassManager().run(APPS[app](KEY, base=8), ctx)
    assert _folds() == 2
    assert ctx.stats["fold_upsample_conv"].changed
    assert not any(n.op == "upsample" for n in g.nodes)
    assert sum(n.op == "pixel_shuffle" for n in g.nodes) == 2


def test_fold_moves_norm_chain_ahead_of_the_shuffle():
    """Bias, fused activation, and an instance norm + relu ending the graph:
    the norm runs on the phase channels with per-phase repeated params."""
    b = GraphBuilder(["x"])
    u = b.add("upsample", "x", name="u", factor=2)
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    c = b.add("conv2d", u, name="c", activation="relu", params={
        "w": jax.random.normal(k1, (8, 4, 3, 3)) * 0.2,
        "b": jax.random.normal(k2, (8,))})
    n = b.add("norm", c, name="n", kind="instance", params={
        "scale": jax.random.normal(k3, (8,)), "bias": jax.random.normal(k4, (8,))})
    g = b.build(b.add("activation", n, name="a", fn="relu"))
    g2 = fold_upsample_conv(g)
    assert [(nd.op, nd.name) for nd in g2.nodes] == [
        ("conv2d", "c"), ("norm", "n"), ("activation", "a"),
        ("pixel_shuffle", "c_shuffle"),
    ]
    assert g2.outputs == ("c_shuffle",)
    assert g2.node("n").attrs["phases"] == 4
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 6, 10))
    with jax.default_matmul_precision("highest"):
        want = compile_plan(g, backend="reference")(g.params, x)
        got = compile_plan(g2, backend="reference")(g2.params, x)
    assert got.shape == (2, 8, 12, 20)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def _upsample_conv(factor=2, k=3, groups=1, consumers=1, **attrs):
    """x [N, 4, H, W] -> upsample(factor) -> conv(k) [+ a second consumer]."""
    def build():
        b = GraphBuilder(["x"])
        u = b.add("upsample", "x", name="u", factor=factor)
        w = jax.random.normal(KEY, (8, 4 // groups, k, k)) * 0.1
        c = b.add("conv2d", u, name="c", params={"w": w}, groups=groups, **attrs)
        if consumers == 2:
            c = b.add("concat", (c, u), name="out", axis=1)
        return b.build(c)

    return build


def _super_resolution():
    return APPS["super_resolution"](KEY, base=8, n_res=2)


@pytest.mark.parametrize("build", [
    _upsample_conv(consumers=2),
    _upsample_conv(stride=2),
    _upsample_conv(k=1),
    _upsample_conv(k=7),
    _upsample_conv(dilation=2),
    _upsample_conv(groups=2),
    _upsample_conv(factor=3),
    _super_resolution,
], ids=["two_consumers", "stride2", "1x1", "7x7", "dilated", "grouped",
        "factor3", "super_resolution"])
def test_fold_leaves_other_patterns_alone(build):
    g = build()
    metrics.registry().reset("graph_rewrites_total")
    g2 = fold_upsample_conv(g)
    assert _folds() == 0
    assert [(n.op, n.name, n.inputs, n.attrs) for n in g2.nodes] == [
        (n.op, n.name, n.inputs, n.attrs) for n in g.nodes
    ]
    for name, p in g.params.items():
        for k, v in p.items():
            np.testing.assert_array_equal(np.asarray(g2.params[name][k]), np.asarray(v))
