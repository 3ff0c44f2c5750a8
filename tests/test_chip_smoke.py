"""CPU rehearsal of chip_smoke.py: it refuses a machine without a TPU, its
phases pass at tiny sizes with interpret-mode kernels, and the compile-cache
helper places the cache where the environment says."""

import importlib.util
import os
import subprocess
import sys

import pytest

from repro.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(tmp_path):
    # its main turns the compile cache on: keep it out of the checkout
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "device: platform=cpu" in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_frames_phase_tiny(smoke):
    sizes = {"style_transfer": (16, 16), "coloring": (16, 16), "super_resolution": (8, 16)}
    rep = smoke.phase_frames(base=8, sizes=sizes, n_frames=3, batch=2, interpret=True)
    for app in sizes:
        assert rep[app]["rel_err"] <= 1e-4, (app, rep[app])
        assert rep[app]["rel_err_highest"] <= 1e-4, (app, rep[app])
        assert rep[app]["conv_steps"] == (
            rep[app]["conv_pallas"] + rep[app]["conv_gemm1x1"]
            + sum(rep[app]["conv_lax"].values())
        )
        assert rep[app]["conv_lax"] == {}  # interpret mode: every conv in Pallas
    # style transfer's instance norms run as counted jnp tails of their convs
    assert rep["style_transfer"]["jnp_route"].get("conv2d/epilogue_norm_instance", 0) > 0
    assert rep["coloring_int8"]["abs_err_vs_f32"] <= smoke.QUANT_ATOL


def test_chip_smoke_decoder_phase_tiny(smoke):
    from repro.configs import smoke_config

    rec = smoke.phase_decoder(
        smoke_config("qwen2.5-3b"), n_seqs=3, prompt_range=(4, 9), new_tokens=3,
        page_size=4, interpret=True,
    )
    assert rec["prefill_logit_err"] <= 1e-4
    assert rec["prefill_logit_err_highest"] <= 1e-4
    assert rec["greedy_match"] == rec["greedy_total"] == 9
    assert rec["failed"] == rec["leaked"] == rec["tick_errors"] == 0


def test_compile_cache_dir_follows_env(monkeypatch):
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"


def test_compile_cache_dir_defaults_into_checkout(monkeypatch):
    import jax

    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
