"""Multi-device tests (subprocess: smoke tests must keep the main process at
ONE device; these re-exec with XLA_FLAGS=--xla_force_host_platform_device_count).

Covers: sharded train step on a small mesh (pjit path used at scale),
gradient compression collective, pipeline parallelism, elastic checkpoint
restore onto a different mesh, and the dry-run machinery itself.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_train_step_small_mesh():
    """pjit train step on (2 data, 2 model): loss decreases, params sharded."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.models import get_model
        from repro.models.sharding import param_pspecs
        from repro.data.pipeline import SyntheticPipeline
        from repro.training.optimizer import AdamWConfig
        from repro.training.train_loop import init_train_state, make_train_step

        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = smoke_config("qwen2.5-3b")
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), param_pspecs(params))
        params = jax.tree.map(jax.device_put, params, p_sh)
        opt_cfg = AdamWConfig(lr=2e-3, total_steps=20, warmup_steps=2)
        state = init_train_state(params, opt_cfg)
        step = jax.jit(make_train_step(model.loss, opt_cfg))
        pipe = SyntheticPipeline(cfg, batch=8, seq=33, seed=0)
        with mesh:
            losses = []
            for _ in range(15):
                b = {k: jax.device_put(v, NamedSharding(mesh, P("data", None)))
                     for k, v in pipe.next().items()}
                state, m = step(state, b)
                losses.append(float(m["ce"]))
        assert losses[-1] < losses[0], losses
        # a TP-sharded weight really is distributed
        w = state.params["layers"][0]["ffn"]["w_gate"]["w"]
        assert len(w.sharding.device_set) == 4 or len(w.sharding.device_set) == 2
        print("OK", losses[0], "->", losses[-1])
    """, devices=4)
    assert "OK" in out


def test_compressed_allreduce_multi_device():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.training.compression import (CompressionConfig,
            make_compressed_allreduce)
        mesh = make_mesh((8,), ("data",))
        tmpl = {"w": jnp.zeros((16, 32))}
        g = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 16, 32))}
        err = {"w": jnp.zeros((8, 16, 32))}
        f = make_compressed_allreduce(mesh, tmpl, cfg=CompressionConfig("int8"))
        mean, err2 = f(g, err)
        true = g["w"].mean(0)
        e1 = float(jnp.abs(mean["w"] - true).max())
        assert e1 < 0.05, e1
        mean2, _ = f(g, err2)
        e2 = float(jnp.abs((mean["w"] + mean2["w"]) / 2 - true).max())
        assert e2 < e1, (e1, e2)   # error feedback reduces bias
        # topk policy
        ft = make_compressed_allreduce(mesh, tmpl, cfg=CompressionConfig("topk", topk_frac=0.5))
        meant, _ = ft(g, {"w": jnp.zeros((8, 16, 32))})
        assert float(jnp.abs(meant["w"]).max()) > 0
        print("OK", e1, e2)
    """)
    assert "OK" in out


def test_pipeline_parallel_grad_exactness():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.training.pipeline_parallel import make_pipelined_loss, pipeline_forward
        mesh = make_mesh((4,), ("pipe",))
        L, D, M, mb = 8, 16, 4, 4
        params = {"w": jax.random.normal(jax.random.PRNGKey(2), (L, D, D)) * 0.2}
        layer_fn = lambda lp, h: jnp.tanh(h @ lp["w"])
        x = jax.random.normal(jax.random.PRNGKey(3), (M, mb, D))
        y = jax.random.normal(jax.random.PRNGKey(4), (M, mb, D))
        out = pipeline_forward(layer_fn, params, x, mesh=mesh)
        ref = x
        for i in range(L):
            ref = jnp.tanh(ref @ params["w"][i])
        assert float(jnp.abs(out - ref).max()) < 1e-5
        loss = make_pipelined_loss(layer_fn, lambda o, t: jnp.mean((o - t) ** 2), mesh=mesh)
        g = jax.grad(loss)(params, x, y)
        def ref_loss(p):
            h = x
            for i in range(L):
                h = jnp.tanh(h @ p["w"][i])
            return jnp.mean((h - y) ** 2)
        g_ref = jax.grad(ref_loss)(params)
        assert float(jnp.abs(g["w"] - g_ref["w"]).max()) < 1e-6
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_elastic_checkpoint_restore_other_mesh(tmp_path):
    """Save on a (4 data, 1 model) mesh, restore onto (2 data, 2 model)."""
    out = _run(f"""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.models import get_model
        from repro.models.sharding import param_pspecs
        from repro.training.checkpoint import restore, save

        cfg = smoke_config("granite-3-2b")
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh_a = make_mesh((4, 1), ("data", "model"))
        sh_a = jax.tree.map(lambda s: NamedSharding(mesh_a, s), param_pspecs(params))
        params_a = jax.tree.map(jax.device_put, params, sh_a)
        save({str(tmp_path)!r}, 7, params_a)

        mesh_b = make_mesh((2, 2), ("data", "model"))
        sh_b = jax.tree.map(lambda s: NamedSharding(mesh_b, s), param_pspecs(params))
        restored, at = restore({str(tmp_path)!r}, params, shardings=sh_b)
        assert at == 7
        d = jax.tree.map(lambda a, b: float(jnp.abs(jnp.asarray(a, jnp.float32)
                                                    - jnp.asarray(b, jnp.float32)).max()),
                         restored, params)
        assert max(jax.tree.leaves(d)) == 0.0
        w = restored["layers"][0]["ffn"]["w_gate"]["w"]
        assert w.sharding.mesh.shape["model"] == 2
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_cell_end_to_end(tmp_path):
    """The actual dry-run driver on one (arch, shape) for both meshes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "granite-3-2b",
         "--shape", "decode_32k", "--mesh", "both", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=1200, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    for mesh in ("single", "multi"):
        with open(tmp_path / f"granite-3-2b__decode_32k__{mesh}.json") as f:
            rec = json.load(f)
        assert rec["ok"], rec.get("error")
        assert rec["chips"] == (256 if mesh == "single" else 512)
        assert rec["cost"]["flops"] > 0
        assert rec["memory"]["argument_bytes"] > 0
    # roofline analysis over the fresh records
    from repro.launch.roofline import analyze_record

    a = analyze_record(rec)
    assert a["dominant"] in ("compute", "memory", "collective")
    assert 0 < a["useful_ratio"] < 10


def test_peaks_table_keyed_by_device_kind():
    """The roofline denominators are looked up by ``device_kind``, with
    their source; a device that is not in the table raises."""
    from repro.launch.mesh import peaks

    v5e = peaks("TPU v5 lite")
    assert v5e.peak_flops == 197e12 and v5e.hbm_bw == 819e9
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


def test_overlapped_collective_matmul():
    """Ring AG-matmul / RS-matmul == gathered reference, grads exact."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.training.collective_matmul import make_overlapped_tp_matmuls
        mesh = make_mesh((4,), ("model",))
        ag, rs = make_overlapped_tp_matmuls(mesh)
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 24)) * 0.1
        assert float(jnp.abs(ag(x, w) - x @ w).max()) < 1e-5
        assert float(jnp.abs(rs(x, w) - x @ w).max()) < 1e-5
        g = jax.grad(lambda x, w: jnp.sum(ag(x, w) ** 2))(x, w)
        g_ref = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2))(x, w)
        assert float(jnp.abs(g - g_ref).max()) < 1e-5
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_embed_gather_on_explicit_mesh():
    """On a mesh with explicit axes (jax.make_mesh's default) the embedding
    gather of a vocab-sharded table by batch-sharded ids is pinned to the
    ids' sharding and equals the unsharded gather."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.layers import embed

        mesh = jax.make_mesh((2, 2), ("data", "model"))
        table = jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16)
        ids = jax.random.randint(jax.random.PRNGKey(0), (8, 5), 0, 64)
        want = embed({"table": table}, ids)
        ts = jax.device_put(table, NamedSharding(mesh, P("model", None)))
        ids_s = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
        got = jax.jit(lambda t, i: embed({"table": t}, i))(ts, ids_s)
        assert got.sharding.spec == P("data", None, None), got.sharding
        assert bool((got == want).all())
        print("OK")
    """, devices=4)
    assert "OK" in out
