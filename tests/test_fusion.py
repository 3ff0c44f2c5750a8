"""Kernel-level fusion (PR 2): the fused-elementwise Pallas kernel vs the
jnp reference handler, GEMM epilogue-program fusion (``fuse_epilogue``),
epilogue-aware memory estimates, and batched plan serving."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import (
    DEFAULT_PIPELINE,
    BatchedPlan,
    GraphBuilder,
    compile_plan,
    fuse_elementwise,
    fuse_epilogue,
    optimize,
)
from repro.core.graph.ir import Graph, Node
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models.cnn import APPS, app_masks
from repro.serving.engine import PlanServer

KEY = jax.random.PRNGKey(0)

APP_INPUTS = {
    "style_transfer": (1, 3, 16, 16),
    "coloring": (1, 1, 16, 16),
    "super_resolution": (1, 3, 8, 8),
}

#: the pipeline with *all* epilogue fusion off (fuse_activation is the
#: single-activation special case of fuse_epilogue) -- the unfused baseline
NO_EPILOGUE = tuple(
    p for p in DEFAULT_PIPELINE if p not in ("fuse_activation", "fuse_epilogue")
)


# --------------------------------------------------------------------------- #
# fused-elementwise Pallas kernel vs reference                                 #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "shape", [(4, 16), (5, 37), (2, 3, 19), (1, 128), (3, 200)]
)
def test_fused_elementwise_kernel_parity_odd_shapes(shape):
    """All step kinds, including layer norm over non-128-multiple dims."""
    d = shape[-1]
    x = jax.random.normal(KEY, shape)
    r = jax.random.normal(jax.random.PRNGKey(1), shape)
    s = jax.random.normal(jax.random.PRNGKey(2), shape)
    scale = jax.random.normal(jax.random.PRNGKey(3), (d,)) * 0.1 + 1.0
    bias = jax.random.normal(jax.random.PRNGKey(4), (d,)) * 0.1
    steps = (("activation", "gelu"), ("add", 0), ("mul", 1), ("norm", 0, 1e-5))
    got = kops.fused_elementwise(x, [r, s], steps, [(scale, bias)], interpret=True)
    want = kref.fused_elementwise_ref(x, [r, s], steps, [(scale, bias)])
    assert got.shape == shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_elementwise_node_kernel_vs_reference_backend():
    """A fused_elementwise node executes through the Pallas kernel on the
    kernel backend and through the jnp interpreter on reference -- same
    answer (graph-step indices, norm params by pkey)."""
    b = GraphBuilder(["x", "y"])
    h = b.add("add", ("x", "y"), name="a1")
    h = b.add("activation", h, name="act1", fn="silu")
    h = b.add("mul", (h, "y"), name="m1")
    h = b.add("norm", h, name="ln1", kind="layer",
              params={"scale": jnp.ones(24) * 1.2, "bias": jnp.ones(24) * 0.3})
    g = fuse_elementwise(b.build(h))
    assert [n.op for n in g.nodes] == ["fused_elementwise"]
    x = jax.random.normal(KEY, (6, 24))
    y = jax.random.normal(jax.random.PRNGKey(1), (6, 24))
    got = compile_plan(g, backend="kernel", interpret=True)(g.params, x, y)
    want = compile_plan(g, backend="reference")(g.params, x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_elementwise_kernel_falls_back_on_broadcast_sides():
    """Sides that only broadcast (not same-shape) cannot stream per-tile;
    the kernel handler must fall back to the interpreter, not crash, and
    the jnp route is counted with its reason."""
    from repro.core.graph.executor import jnp_route_counts

    n1 = Node(op="fused_elementwise", name="f", inputs=("x", "y"),
              attrs={"steps": (("add", 1), ("activation", "relu"))})
    g = Graph(nodes=[n1], inputs=("x", "y"), outputs=("f",))
    x = jax.random.normal(KEY, (4, 16))
    y = jax.random.normal(jax.random.PRNGKey(1), (16,))  # broadcasts over rows
    before = jnp_route_counts().get("fused_elementwise/broadcast_side", 0)
    got = compile_plan(g, backend="kernel", interpret=True)(g.params, x, y)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jax.nn.relu(x + y)), rtol=1e-6
    )
    assert jnp_route_counts()["fused_elementwise/broadcast_side"] == before + 1


@pytest.mark.parametrize("app", list(APPS))
def test_app_plans_kernel_vs_reference_backend(app):
    """Full compiled plans (epilogue attrs included) agree across backends
    on the paper's three apps (Pallas in interpret mode)."""
    g = APPS[app](KEY, base=8)
    masks, structures = app_masks(g, app, sparsity=0.5)
    go = optimize(g, masks, structures)
    x = jax.random.normal(jax.random.PRNGKey(1), APP_INPUTS[app])
    got = compile_plan(go, backend="kernel", interpret=True)(go.params, x)
    want = compile_plan(go, backend="reference")(go.params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_fused_elementwise_tuning_cache_key():
    cache = kops.tuning_cache()
    prev_enabled, prev_entries = cache.enabled, dict(cache.entries)
    cache.clear()
    cache.enabled = False
    try:
        x = jax.random.normal(KEY, (8, 48))
        kops.fused_elementwise(x, [x], (("add", 0),), interpret=True)
        # side/norm counts are part of the key: same-shape programs with
        # different operand counts must never share a swept winner
        key = kops.TuningCache.key(
            "fused_elementwise", 8, 48, 1, jnp.float32, "ew+s1n0", True
        )
        assert key in cache.entries
        # interpret mode seeds a single full-M tile (one grid step: each
        # step costs ~1 ms of Python there), not the hw 128-row default
        assert cache.entries[key].blocks == (8,)
    finally:
        cache.enabled = prev_enabled
        cache.entries = prev_entries


# --------------------------------------------------------------------------- #
# fuse_epilogue                                                                #
# --------------------------------------------------------------------------- #


def _linear_chain_graph(n=32):
    b = GraphBuilder(["x", "r"])
    l1 = b.add("linear", "x", name="l1",
               params={"w": jax.random.normal(KEY, (n, n)) * 0.1,
                       "b": jnp.zeros(n)})
    h = b.add("activation", l1, name="act", fn="gelu")
    h = b.add("add", (h, "r"), name="res")
    return b.build(h)


def test_fuse_epilogue_folds_into_linear():
    g = _linear_chain_graph()
    gf = fuse_epilogue(g)
    assert [n.op for n in gf.nodes] == ["linear"]
    fused = gf.nodes[0]
    assert fused.name == "res"  # keeps the tail's name
    assert fused.attrs["epilogue"] == (("activation", "gelu"), ("add", 1))
    assert "w" in gf.params["res"] and "l1" not in gf.params
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    r = jax.random.normal(jax.random.PRNGKey(2), (4, 32))
    want = compile_plan(g, backend="reference")(g.params, x, r)
    for backend, interp in (("reference", None), ("kernel", True)):
        got = compile_plan(gf, backend=backend, interpret=interp)(gf.params, x, r)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_fuse_epilogue_fixpoint_conv_norm_act_add():
    """conv -> instance norm -> relu -> residual add collapses to one conv
    node with a 3-step epilogue (the style-transfer block shape)."""
    b = GraphBuilder(["x"])
    c0 = b.add("conv2d", "x", name="c0",
               params={"w": jax.random.normal(KEY, (4, 4, 3, 3)) * 0.1})
    c1 = b.add("conv2d", c0, name="c1",
               params={"w": jax.random.normal(jax.random.PRNGKey(1), (4, 4, 3, 3)) * 0.1})
    h = b.add("norm", c1, name="in1", kind="instance",
              params={"scale": jnp.ones(4) * 1.4, "bias": jnp.ones(4) * 0.1})
    h = b.add("activation", h, name="a1", fn="relu")
    h = b.add("add", (c0, h), name="res")
    g = b.build(h)
    gf = fuse_epilogue(g)
    ops = [n.op for n in gf.nodes]
    assert ops == ["conv2d", "conv2d"], ops
    epi = gf.nodes[-1].attrs["epilogue"]
    assert [s[0] for s in epi] == ["norm_instance", "activation", "add"]
    assert "e0_scale" in gf.params["res"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 8, 8))
    got = compile_plan(gf, backend="reference")(gf.params, x)
    want = compile_plan(g, backend="reference")(g.params, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fuse_epilogue_respects_fanout_and_outputs():
    # fanout: the GEMM output feeds two consumers -> no fold
    b = GraphBuilder(["x"])
    l1 = b.add("linear", "x", name="l1", params={"w": jnp.eye(8)})
    a1 = b.add("activation", l1, name="a1", fn="relu")
    a2 = b.add("activation", l1, name="a2", fn="tanh")
    out = b.add("add", (a1, a2), name="out")
    g = b.build(out)
    assert any(n.op == "activation" for n in fuse_epilogue(g).nodes)
    # graph output: the GEMM's name is externally visible -> no fold
    b = GraphBuilder(["x"])
    l1 = b.add("linear", "x", name="l1", params={"w": jnp.eye(8)})
    a1 = b.add("activation", l1, name="a1", fn="relu")
    g = b.build((l1, a1))
    assert len(fuse_epilogue(g).nodes) == 2


def test_fuse_epilogue_skips_step_referencing_raw_gemm_output():
    """relu(l1) + l1 needs the pre-step value as a side: not expressible as
    a running-value epilogue, so the fused_elementwise node must survive."""
    b = GraphBuilder(["x"])
    l1 = b.add("linear", "x", name="l1", params={"w": jnp.eye(8)})
    a1 = b.add("activation", l1, name="a1", fn="relu")
    res = b.add("add", (a1, l1), name="res")
    g = fuse_elementwise(b.build(res))
    assert [n.op for n in g.nodes] == ["linear", "fused_elementwise"]
    gf = fuse_epilogue(g)
    assert [n.op for n in gf.nodes] == ["linear", "fused_elementwise"]


@pytest.mark.parametrize("app", list(APPS))
def test_fuse_epilogue_reduces_steps_and_matches_on_apps(app):
    """Acceptance: epilogue fusion shrinks every demo app's plan and the
    outputs match the unfused plan to f32 tolerance."""
    g = APPS[app](KEY, base=16)
    masks, structures = app_masks(g, app, sparsity=0.5)
    go = optimize(g, masks, structures)
    go0 = optimize(g, masks, structures, pipeline=NO_EPILOGUE)
    plan = compile_plan(go, backend="reference")
    plan0 = compile_plan(go0, backend="reference")
    assert len(plan.steps) < len(plan0.steps), (len(plan.steps), len(plan0.steps))
    x = jax.random.normal(jax.random.PRNGKey(1), APP_INPUTS[app])
    np.testing.assert_allclose(
        np.asarray(plan(go.params, x)),
        np.asarray(plan0(go0.params, x)),
        rtol=1e-5, atol=1e-5,
    )


def test_memory_estimate_epilogue_not_double_counted():
    """Folded steps must not appear as resident intermediates: the fused
    plan's estimate drops the follower buffers and its peak never exceeds
    the unfused plan's."""
    g = _linear_chain_graph(n=64)
    gf = fuse_epilogue(g)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    r = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    mem0 = compile_plan(g, backend="reference").memory_estimate(x, r)
    mem1 = compile_plan(gf, backend="reference").memory_estimate(x, r)
    names1 = [n for n, _, _ in mem1["per_step"]]
    assert names1 == ["res"]  # l1/act intermediates gone from the schedule
    assert mem1["peak_activation_bytes"] <= mem0["peak_activation_bytes"]
    assert mem1["out_structs"][0].shape == (8, 64)


# --------------------------------------------------------------------------- #
# batched plan execution + serving                                             #
# --------------------------------------------------------------------------- #


def _small_app_plan():
    g = APPS["super_resolution"](KEY, base=8)
    go = optimize(g)
    return go, compile_plan(go, backend="reference")


def test_batched_plan_pads_remainder_and_matches_plain_plan():
    go, plan = _small_app_plan()
    bp = plan.batched(2)
    assert isinstance(bp, BatchedPlan)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 3, 8, 8))
    got = bp(go.params, x)
    want = plan(go.params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert bp.last_stats == {"frames": 5, "batches": 3, "padded_frames": 1}


def test_batched_plan_exact_multiple_no_padding():
    go, plan = _small_app_plan()
    bp = plan.batched(2)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 3, 8, 8))
    bp(go.params, x)
    assert bp.last_stats == {"frames": 4, "batches": 2, "padded_frames": 0}


def test_batched_plan_via_vmap_matches_native():
    b = GraphBuilder(["x"])
    h = b.add("linear", "x", name="l1",
              params={"w": jax.random.normal(KEY, (16, 16)) * 0.1})
    h = b.add("activation", h, name="a1", fn="relu")
    g = b.build(h)
    plan = compile_plan(g, backend="reference")
    x = jax.random.normal(jax.random.PRNGKey(1), (7, 4, 16))
    got = plan.batched(3, via_vmap=True)(g.params, x)
    want = plan.batched(3)(g.params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_batched_plan_rejects_bad_args():
    go, plan = _small_app_plan()
    with pytest.raises(ValueError, match="batch_size"):
        plan.batched(0)
    with pytest.raises(TypeError, match="at least one input"):
        plan.batched(2)({})
    with pytest.raises(ValueError, match="empty macro-batch"):
        plan.batched(2)(go.params, jnp.zeros((0, 3, 8, 8)))


def test_plan_server_queue_and_stats():
    go, plan = _small_app_plan()
    server = PlanServer(plan, go.params, batch_size=4)
    frames = [jax.random.normal(jax.random.PRNGKey(i), (3, 8, 8)) for i in range(6)]
    for f in frames:
        server.submit(f)
    assert server.pending == 6
    out = server.flush()
    assert server.pending == 0
    assert out.shape[0] == 6
    assert server.stats == {
        "frames": 6, "batches": 2, "padded_frames": 2, "deadline_flushes": 0,
    }
    want = plan(go.params, jnp.stack(frames))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert server.flush() is None  # empty queue is a no-op
    with pytest.raises(TypeError, match="inputs per frame"):
        server.submit(frames[0], frames[0])


def test_plan_server_close_flushes_partial_batch():
    """Queued frames must never be dropped: close() drains a partial tail
    batch (smaller than batch_size) and refuses further submits."""
    go, plan = _small_app_plan()
    server = PlanServer(plan, go.params, batch_size=4)
    frames = [jax.random.normal(jax.random.PRNGKey(i), (3, 8, 8)) for i in range(3)]
    for f in frames:
        server.submit(f)
    assert server.pending == 3  # strictly less than one full batch
    out = server.close()
    assert server.pending == 0 and server.closed
    assert out.shape[0] == 3
    want = plan(go.params, jnp.stack(frames))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert server.stats["frames"] == 3 and server.stats["padded_frames"] == 1
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(frames[0])
    assert server.close() is None  # idempotent


def test_plan_server_context_manager_drains_queue():
    go, plan = _small_app_plan()
    with PlanServer(plan, go.params, batch_size=4) as server:
        server.submit(jax.random.normal(KEY, (3, 8, 8)))
        assert server.pending == 1
    assert server.closed and server.pending == 0
    assert server.stats["frames"] == 1  # the exit flush ran it


def test_plan_server_flush_after_deadline_flushes_partial_batch():
    """Low-traffic serving: once the oldest queued frame has waited past the
    deadline, the next submit auto-flushes the partial batch instead of
    blocking on batch fill."""
    go, plan = _small_app_plan()
    now = [0.0]
    server = PlanServer(
        plan, go.params, batch_size=4, flush_after=1.0, clock=lambda: now[0]
    )
    f0 = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 8))
    f1 = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8))
    server.submit(f0)
    assert server.pending == 1 and not server.completed  # under deadline
    now[0] = 0.5
    assert server.poll() is None  # still under deadline
    now[0] = 1.2  # the *oldest* frame is now past the deadline
    server.submit(f1)  # joins the flush triggered by its own submit
    assert server.pending == 0
    assert len(server.completed) == 1
    (out,) = server.drain_completed()  # hand over + clear the buffer
    assert not server.completed
    assert out.shape[0] == 2
    want = plan(go.params, jnp.stack([f0, f1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert server.stats["deadline_flushes"] == 1
    assert server.stats["frames"] == 2 and server.stats["padded_frames"] == 2


def test_plan_server_flush_after_poll_without_submit():
    """A lone frame must never be stranded: an idle-loop poll() flushes it
    once the deadline passes, and the timer re-arms for the next frame."""
    go, plan = _small_app_plan()
    now = [0.0]
    server = PlanServer(
        plan, go.params, batch_size=4, flush_after=0.5, clock=lambda: now[0]
    )
    assert server.poll() is None  # empty queue: no-op
    server.submit(jax.random.normal(KEY, (3, 8, 8)))
    now[0] = 0.6
    out = server.poll()
    assert out is not None and out.shape[0] == 1
    assert server.completed == []  # poll hands outputs back, never buffers
    assert server.poll() is None  # queue drained; deadline timer reset
    # a fresh frame restarts the deadline from its own submit time
    server.submit(jax.random.normal(KEY, (3, 8, 8)))
    assert server.poll() is None
    now[0] = 1.2
    assert server.poll() is not None
    assert server.stats["deadline_flushes"] == 2


def test_plan_server_flush_after_close_interaction():
    """close() drains regardless of the deadline (queued frames are never
    dropped), and a closed server's poll() is a no-op."""
    go, plan = _small_app_plan()
    now = [0.0]
    server = PlanServer(
        plan, go.params, batch_size=4, flush_after=10.0, clock=lambda: now[0]
    )
    f0 = jax.random.normal(KEY, (3, 8, 8))
    server.submit(f0)
    out = server.close()  # deadline nowhere near expired: close still drains
    assert out is not None and out.shape[0] == 1 and server.closed
    assert server.stats["deadline_flushes"] == 0  # manual close, not deadline
    assert server.poll() is None  # closed server: no-op
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(f0)


# --------------------------------------------------------------------------- #
# PBCSR band kernel: epilogue step programs in-tile                            #
# --------------------------------------------------------------------------- #


def _pbcsr_setup(key, k=256, n=384, m=64, sparsity=0.5, balanced=True):
    from repro.core.pruning import Block, project
    from repro.core.sparse import PBCSR

    w = jax.random.normal(key, (k, n)) * 0.05
    wp, mask = project(w, Block(sparsity, bm=128, bn=128, balanced=balanced))
    fmt = PBCSR.from_dense(wp, mask, 128, 128)
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k))
    return wp, fmt, x


def test_bsr_epilogue_program_matches_jnp_tail():
    wp, fmt, x = _pbcsr_setup(jax.random.PRNGKey(7))
    n = wp.shape[1]
    b = jax.random.normal(jax.random.PRNGKey(8), (n,))
    side = jax.random.normal(jax.random.PRNGKey(9), (x.shape[0], n))
    steps = (("add", 0), ("activation", "gelu"), ("mul", 0))
    got = kops.bsr_matmul(
        x, fmt.values, fmt.block_rows, b, activation="relu",
        epilogue=steps, epilogue_sides=(side,),
    )
    tail = kops.bsr_matmul(x, fmt.values, fmt.block_rows, b, activation="relu")
    want = kref.apply_steps_ref(tail, steps, [side])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_bsr_epilogue_banded_with_empty_band():
    """Band dispatch slices the epilogue sides per band; a zero-count band
    (pure bias/activation/epilogue of zeros) must honor the program too."""
    from repro.core.sparse import PBCSR, block_mask, plan_reorder, apply_column_perm
    from repro.core.pruning import Block, project

    k, n, m = 512, 768, 64
    w = jax.random.normal(jax.random.PRNGKey(3), (k, n)) * 0.05
    wp, mask = project(w, Block(0.6, bm=128, bn=128, balanced=False))
    # force one fully-dead block column so a zero-count band exists
    mask = mask.at[:, :128].set(0)
    wp = wp * mask
    bm_ = np.asarray(block_mask(mask, 128, 128))
    plan = plan_reorder(bm_, max_bands=3)
    w_perm = apply_column_perm(wp, plan.order, 128)
    m_perm = apply_column_perm(mask, plan.order, 128)
    fmt = PBCSR.from_dense(w_perm, m_perm, 128, 128)
    bands = [(b.start, b.stop, b.count) for b in plan.bands]
    assert any(c == 0 for _, _, c in bands)
    x = jax.random.normal(KEY, (m, k))
    b = jax.random.normal(jax.random.PRNGKey(8), (n,))
    side = jax.random.normal(jax.random.PRNGKey(9), (m, n))
    steps = (("add", 0), ("activation", "tanh"))
    got = kops.bsr_matmul(
        x, fmt.values, fmt.block_rows, b, bands=bands,
        epilogue=steps, epilogue_sides=(side,),
    )
    want = kref.apply_steps_ref(kref.matmul_ref(x, w_perm, b), steps, [side])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_bsr_epilogue_tunes_under_its_own_key():
    wp, fmt, x = _pbcsr_setup(jax.random.PRNGKey(11))
    n = wp.shape[1]
    side = jax.random.normal(jax.random.PRNGKey(9), (x.shape[0], n))
    cache = kops.tuning_cache()
    prev = dict(cache.entries)
    try:
        kops.bsr_matmul(x, fmt.values, fmt.block_rows)
        kops.bsr_matmul(
            x, fmt.values, fmt.block_rows,
            epilogue=(("add", 0),), epilogue_sides=(side,),
        )
        keys = [k_ for k_ in cache.entries if k_.startswith("bsr_matmul|")]
        fmts = {k_.split("|")[3] for k_ in keys}
        assert "pbcsr" in fmts and "pbcsr+e1s1" in fmts
    finally:
        cache.entries = prev


def test_pbcsr_plan_executes_epilogue_in_kernel(monkeypatch):
    """A sparse_linear(pbcsr) node with a tile-fusable epilogue must reach
    the Pallas kernel as a step program, not the jnp tail."""
    from repro.core.pruning import Block, project
    from repro.core.sparse import PBCSR

    k, n = 256, 256
    w = jax.random.normal(KEY, (k, n)) * 0.05
    wp, mask = project(w, Block(0.5, bm=128, bn=128))
    fmt = PBCSR.from_dense(wp, mask, 128, 128)
    nodes = [
        Node("sparse_linear", "sp", ("x",), {"format": "pbcsr"}),
        Node("add", "res", ("sp", "skip")),
        Node("activation", "act", ("res",), {"fn": "relu"}),
    ]
    g = Graph(
        nodes=nodes, inputs=("x", "skip"), outputs=("act",),
        params={"sp": {"values": fmt.values, "block_rows": fmt.block_rows}},
    )
    gf = fuse_epilogue(g)
    (node,) = [nd for nd in gf.nodes if nd.op == "sparse_linear"]
    assert node.attrs["epilogue"] == (("add", 1), ("activation", "relu"))
    seen = {}
    real = kops.bsr_matmul

    def spy(*a, **kw):
        seen.update(kw)
        return real(*a, **kw)

    monkeypatch.setattr(kops, "bsr_matmul", spy)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, k))
    skip = jax.random.normal(jax.random.PRNGKey(3), (64, n))
    got = compile_plan(gf, backend="kernel")(gf.params, x, skip)
    assert seen.get("epilogue"), "epilogue did not reach the Pallas kernel"
    want = compile_plan(g, backend="reference")(g.params, x, skip)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
