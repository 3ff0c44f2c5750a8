"""Kernel-level benchmarks.

Wall-clock of Pallas interpret mode measures the Python interpreter, not the
algorithm, so this bench reports what is *portable* from this container:

1. correctness-gated compute scaling: packed-BSR buffer sizes and MXU-tile
   counts vs density (the compute contract the TPU kernel executes);
2. measured XLA-CPU wall time of the column-compacted GEMM vs dense (the
   gather+smaller-GEMM path is real on any backend);
3. storage: PBCSR vs CSR vs dense across sparsities (the paper's
   "beats CSR" claim);
4. block-size auto-tuning: with the tuning cache enabled, sweep the candidate
   grid once per GEMM shape and report the chosen blocks (the paper's
   "parameter auto-tuning" applied to Pallas tiling);
5. fusion: the fused-elementwise Pallas kernel vs the unfused jnp chain
   (parity always asserted; the wall-clock win asserted on real hardware
   only) and ``fuse_epilogue`` plan-step reduction + parity on the three
   demo apps.  Results land in ``results/BENCH_fusion.json`` so the perf
   trajectory is recorded across PRs.
6. quant: the INT8 qmatmul kernel (W8A8 + W8-only) vs the fp32 GEMM --
   bytes-moved and parity in every mode, wall-clock speedup asserted on
   real hardware only -- and the three demo apps end-to-end through the
   ``quantize`` pass (fp32-vs-int8 plan ms, weight bytes, max-abs-error,
   parity gated at 5e-2).  Results land in ``results/BENCH_quant.json``.
7. conv: the implicit-GEMM Pallas conv2d (dense f32, channel-pruned, W8,
   W8A8 schemes) vs the lax.conv baseline, plus the three demo apps through
   kernel-backend plans -- every conv must lower through the Pallas kernel
   (zero fallbacks) at parity with the jnp reference plan, step counts at or
   below the PR 2 baseline.  Results land in ``results/BENCH_conv.json``.

``--smoke`` shrinks every shape so CI can exercise the full path without a
TPU (also reachable via ``make bench-smoke``).

Timing methodology: every sample dispatches the jitted callable and blocks
on the result via ``jax.block_until_ready``, so a sample covers dispatch +
device execution and never measures async dispatch alone.  ``--warmup``
extra calls run first (JIT compile + caches) and are discarded; ``--repeat``
timed samples are reduced with the median (robust to scheduler noise).
Baselines (lax.conv / fp32 GEMM) are timed ONCE per shape and shared across
every scheme row of that shape, so scheme-to-scheme ratios within a shape
are against the identical baseline sample.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pruning import Block, Column, project
from repro.core.sparse import CSR, ColumnCompact, PBCSR, dense_nbytes
from repro.kernels import bsr_matmul, matmul, ref
from repro.kernels import ops as kops
from repro.utils.compile_cache import enable_compile_cache

K, N, M = 2048, 2048, 256

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


#: global overrides set by --repeat / --warmup (None -> per-bench default:
#: 7 samples, or 3 under --smoke; 1 warmup call)
REPEAT: int | None = None
WARMUP: int | None = None


def _median_time(fn, *args, reps=7):
    reps = REPEAT if REPEAT is not None else reps
    for _ in range(max(1, WARMUP if WARMUP is not None else 1)):
        jax.block_until_ready(fn(*args))  # compile + warm caches, discarded
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))  # sample = dispatch + execution
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_bsr_compute_scaling(k=K, n=N, m=M):
    print("kernel_bsr,density,mxu_tiles,values_bytes,correct")
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n)) * 0.02
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    for sp in (0.0, 0.25, 0.5, 0.75):
        if sp == 0.0:
            tiles = (k // 128) * (n // 128)
            vb = dense_nbytes((k, n), jnp.float32)
            ok = True
        else:
            wp, mask = project(w, Block(sp, bm=128, bn=128))
            fmt = PBCSR.from_dense(wp, mask, 128, 128)
            got = bsr_matmul(x[:128], fmt.values, fmt.block_rows)
            want = ref.matmul_ref(x[:128], wp)
            ok = bool(np.allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-3))
            tiles = fmt.n_blocks
            vb = int(fmt.values.size) * 4
        print(f"kernel_bsr,{1-sp:.2f},{tiles},{vb},{ok}")


def bench_colcompact_walltime(k=K, n=N, m=M):
    print("kernel_colpack,density,ms_dense,ms_colpack,speedup")
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n)) * 0.02
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    f_dense = jax.jit(lambda x, w: x @ w)
    t_dense = _median_time(f_dense, x, w)
    for sp in (0.5, 0.75):
        wp, mask = project(w, Column(sp))
        cc = ColumnCompact.from_dense(wp, mask)
        f_cc = jax.jit(lambda x, v, k: jnp.take(x, k, axis=-1) @ v)
        t_cc = _median_time(f_cc, x, cc.values, cc.kept)
        err = float(jnp.abs(f_cc(x, cc.values, cc.kept) - x @ wp).max())
        assert err < 1e-3, err
        print(f"kernel_colpack,{1-sp:.2f},{t_dense*1e3:.2f},{t_cc*1e3:.2f},{t_dense/t_cc:.2f}")


def bench_storage(side=1024):
    print("storage,sparsity,dense_bytes,csr_bytes,pbcsr_bytes,pbcsr_vs_csr")
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (side, side)))
    for sp in (0.5, 0.75, 0.9):
        wp, mask = project(jnp.asarray(w), Block(sp, bm=128, bn=128, balanced=False))
        pb = PBCSR.from_dense(wp, mask, 128, 128)
        csr = CSR.from_dense(np.asarray(wp), np.asarray(mask))
        d = dense_nbytes((side, side), jnp.float32)
        print(f"storage,{sp},{d},{csr.nbytes},{pb.nbytes},{csr.nbytes/max(pb.nbytes,1):.2f}x")


def bench_tuned_blocks(shapes=None):
    """Enable the tuning cache, trigger one sweep per shape, report winners.

    Shapes stay small because the container runs Pallas in interpret mode;
    on real TPU hardware the same sweep times the compiled kernels.
    """
    cache = kops.tuning_cache()
    prev_enabled, prev_entries = cache.enabled, dict(cache.entries)
    cache.clear()
    cache.enabled = True
    try:
        shapes = shapes or [(8, 256, 256), (32, 512, 256), (8, 128, 512)]
        for m, n, k in shapes:
            x = jax.random.normal(jax.random.PRNGKey(0), (m, k)) * 0.1
            w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.1
            matmul(x, w)  # miss -> sweep -> cached
            matmul(x, w)  # hit
        # the fused-elementwise kernel tunes under its own op key
        x = jax.random.normal(jax.random.PRNGKey(2), (shapes[0][0], 256)) * 0.1
        kops.fused_elementwise(x, [x], (("add", 0), ("activation", "relu")))
        assert cache.sweeps == len(shapes) + 1, (cache.sweeps, len(shapes) + 1)
        print("tuning," + cache.report().replace("\n", "\ntuning,"))
        out = os.environ.get("REPRO_TUNE_CACHE")
        if out:
            print(f"tuning,saved,{cache.save(out)}")
    finally:
        cache.enabled = prev_enabled
        cache.entries = prev_entries


# --------------------------------------------------------------------------- #
# fusion: fused-elementwise kernel + epilogue-program plans                    #
# --------------------------------------------------------------------------- #


def _elementwise_cases(smoke: bool):
    """(name, [M, D] view shape) pairs at table-1-ish scales: the NCHW case
    mirrors a demo-app activation map flattened over its last dim, the LM
    case a transformer residual stream."""
    if smoke:
        return [("app_nchw", (64, 128)), ("lm_residual", (32, 256))]
    return [("app_nchw", (4096, 128)), ("lm_residual", (256, 2048))]


def bench_fusion(smoke: bool = False, out_path: str | None = None) -> dict:
    interpret = kops.interpret_default()
    record: dict = {
        "mode": "interpret" if interpret else "hw",
        "smoke": smoke,
        "elementwise": [],
        "epilogue_plans": [],
    }
    print("fusion,case,steps,ms_unfused,ms_fused,speedup,bytes_unfused,bytes_fused,max_err")
    # 4-step program: activation -> residual add -> gating mul -> layer norm
    for name, (m, d) in _elementwise_cases(smoke):
        x = jax.random.normal(jax.random.PRNGKey(0), (m, d))
        r = jax.random.normal(jax.random.PRNGKey(1), (m, d))
        s = jax.random.normal(jax.random.PRNGKey(2), (m, d))
        scale, bias = jnp.ones(d) * 1.1, jnp.zeros(d) + 0.1
        steps = (("activation", "gelu"), ("add", 0), ("mul", 1), ("norm", 0, 1e-5))

        unfused = jax.jit(
            lambda x, r, s, scale, bias: ref.fused_elementwise_ref(
                x, [r, s], steps, [(scale, bias)]
            )
        )
        fused = jax.jit(
            lambda x, r, s, scale, bias: kops.fused_elementwise(
                x, [r, s], steps, [(scale, bias)]
            )
        )
        err = float(jnp.abs(fused(x, r, s, scale, bias) - unfused(x, r, s, scale, bias)).max())
        assert err < 1e-4, (name, err)  # parity gates the bench in every mode
        t_un = _median_time(unfused, x, r, s, scale, bias, reps=3 if smoke else 7)
        t_fu = _median_time(fused, x, r, s, scale, bias, reps=3 if smoke else 7)
        nb = x.size * x.dtype.itemsize
        # unfused: each step reads the running value (+1 side for add/mul)
        # and writes it back; fused: one read of x + sides, one write.
        bytes_unfused = sum(
            (3 if st[0] in ("add", "mul") else 2) * nb for st in steps
        )
        bytes_fused = (1 + 2) * nb + nb  # x + two sides in, one out
        speedup = t_un / t_fu
        if not interpret:  # interpret timings measure Python, not silicon
            assert speedup > 1.0, (name, speedup)
        row = {
            "case": name, "shape": [m, d], "n_steps": len(steps),
            "ms_unfused": t_un * 1e3, "ms_fused": t_fu * 1e3,
            "speedup": speedup, "bytes_unfused": bytes_unfused,
            "bytes_fused": bytes_fused, "max_err": err,
        }
        record["elementwise"].append(row)
        print(
            f"fusion,{name},{len(steps)},{t_un*1e3:.3f},{t_fu*1e3:.3f},"
            f"{speedup:.2f},{bytes_unfused},{bytes_fused},{err:.2e}"
        )

    # fuse_epilogue: plan-step reduction + parity on the paper's three apps
    from repro.core.graph import DEFAULT_PIPELINE, compile_plan, optimize
    from repro.models.cnn import APPS, app_masks

    no_epi = tuple(
        p for p in DEFAULT_PIPELINE if p not in ("fuse_activation", "fuse_epilogue")
    )
    size = 16 if smoke else 64
    base = 8 if smoke else 16
    print("fusion_epilogue,app,steps_unfused,steps_fused,max_err")
    for app in APPS:
        g = APPS[app](jax.random.PRNGKey(0), base=base)
        masks, structures = app_masks(g, app, sparsity=0.5)
        go = optimize(g, masks, structures)
        go0 = optimize(g, masks, structures, pipeline=no_epi)
        plan = compile_plan(go, backend="reference")
        plan0 = compile_plan(go0, backend="reference")
        c_in = 1 if app == "coloring" else 3
        x = jax.random.normal(jax.random.PRNGKey(1), (1, c_in, size, size))
        err = float(jnp.abs(plan(go.params, x) - plan0(go0.params, x)).max())
        assert len(plan.steps) < len(plan0.steps), app
        assert err < 1e-4, (app, err)
        row = {
            "app": app, "steps_unfused": len(plan0.steps),
            "steps_fused": len(plan.steps), "max_err": err,
        }
        record["epilogue_plans"].append(row)
        print(f"fusion_epilogue,{app},{len(plan0.steps)},{len(plan.steps)},{err:.2e}")

    # smoke numbers are CI plumbing, not perf data: never clobber the
    # cross-PR trajectory artifact with them
    default_name = "BENCH_fusion_smoke.json" if smoke else "BENCH_fusion.json"
    out_path = out_path or os.path.join(RESULTS_DIR, default_name)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"fusion,saved,{os.path.abspath(out_path)}")
    return record


# --------------------------------------------------------------------------- #
# quant: INT8 kernels + quantized demo-app plans                               #
# --------------------------------------------------------------------------- #


def bench_quant(smoke: bool = False, out_path: str | None = None) -> dict:
    from repro.core.graph import PassContext, PassManager, compile_plan, optimize
    from repro.kernels import qmatmul
    from repro.models.cnn import APP_ACT_SKIP, APP_QUANT_SKIP, APPS, app_masks
    from repro.quant import QTensor, calibrate_plan

    interpret = kops.interpret_default()
    record: dict = {
        "mode": "interpret" if interpret else "hw",
        "smoke": smoke,
        "kernels": [],
        "apps": [],
    }

    # kernel-level: W8A8 / W8-only qmatmul vs the fp32 Pallas GEMM.
    # interpret-mode wall-clock measures Python, so shapes stay modest there;
    # bytes-moved is the portable story (weight stream shrinks 4x).
    m, n, k = (64, 128, 128) if smoke else (256, 512, 512)
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k)) * 0.5
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.05
    qt = QTensor.from_float(w, axis=1)
    x_scale = float(jnp.max(jnp.abs(x))) / 127.0
    f32 = jax.jit(lambda x, w: matmul(x, w))
    t_f32 = _median_time(f32, x, w, reps=3 if smoke else 7)
    want = ref.matmul_ref(x, w)
    print("quant,scheme,MxNxK,ms_fp32,ms_int8,speedup,w_bytes_fp32,w_bytes_int8,max_err")
    for scheme, kw in (("w8", {}), ("w8a8", {"x_scale": x_scale})):
        fq = jax.jit(lambda x, v, s: qmatmul(x, v, s, **kw))
        t_q = _median_time(fq, x, qt.values, qt.scale, reps=3 if smoke else 7)
        err = float(jnp.abs(fq(x, qt.values, qt.scale) - want).max())
        # parity vs fp32 gates the bench in every mode (quantization noise
        # bounded by the per-channel scales); exactness vs the int8 oracle
        # is covered in tests/test_quant.py
        assert err <= 5e-2, (scheme, err)
        speedup = t_f32 / t_q
        if not interpret:  # interpret timings measure Python, not silicon
            assert speedup > 1.0, (scheme, speedup)
        row = {
            "scheme": scheme, "shape": [m, n, k],
            "ms_fp32": t_f32 * 1e3, "ms_int8": t_q * 1e3, "speedup": speedup,
            "w_bytes_fp32": int(w.size) * 4, "w_bytes_int8": qt.nbytes,
            "max_err": err,
        }
        record["kernels"].append(row)
        print(
            f"quant,{scheme},{m}x{n}x{k},{t_f32*1e3:.3f},{t_q*1e3:.3f},"
            f"{speedup:.2f},{int(w.size)*4},{qt.nbytes},{err:.2e}"
        )

    # app-level: calibrate -> quantize pass -> quantized plan vs fp32 plan.
    # CPU times the jnp reference executions of both (XLA-real); on TPU the
    # quant backend runs the INT8 Pallas kernels.  This subsection is a
    # *correctness* gate, so it runs at the fixed regression scale and on
    # the canonical probe shared with tests/test_quant.py in every mode:
    # max-abs error is the max over all output pixels (fat-tailed across
    # probes and growing with frame area), so gating one pinned
    # configuration keeps the 5e-2 contract a meaningful regression signal
    # across PRs (full mode only adds timing reps).
    shapes = {
        "style_transfer": (1, 3, 16, 16),
        "coloring": (1, 1, 16, 16),
        "super_resolution": (1, 3, 8, 8),
    }
    key = jax.random.PRNGKey(0)
    backend = "reference" if interpret else "quant"
    f32_backend = "reference" if interpret else "kernel"
    print("quant_app,app,backend,ms_fp32,ms_int8,w_bytes_fp32,w_bytes_int8,ratio,max_err")
    for app in APPS:
        g = APPS[app](key, base=8)
        masks, structures = app_masks(g, app, sparsity=0.5)
        go = optimize(g, masks, structures)
        plan_f = compile_plan(go, backend=f32_backend)
        batches = [
            jax.random.normal(jax.random.fold_in(key, i), shapes[app])
            for i in range(2)
        ]
        plan_ref = compile_plan(go, backend="reference")
        table = calibrate_plan(plan_ref, go.params, batches)
        gq = PassManager(("quantize",)).run(
            go,
            PassContext(
                calibration=table, quant_skip=APP_QUANT_SKIP[app],
                act_quant_skip=APP_ACT_SKIP[app],
            ),
        )
        plan_q = compile_plan(gq, backend=backend)
        x = jax.random.normal(jax.random.fold_in(key, 99), shapes[app])
        err = float(jnp.abs(plan_q(gq.params, x) - plan_f(go.params, x)).max())
        assert err <= 5e-2, (app, err)  # parity gates the bench in every mode
        mem_f = plan_f.memory_estimate(x)
        mem_q = plan_q.memory_estimate(x)
        ratio = mem_f["param_bytes"] / mem_q["param_bytes"]
        assert ratio >= 3.0, (app, ratio)
        jf = jax.jit(lambda p, x: plan_f(p, x))
        jq = jax.jit(lambda p, x: plan_q(p, x))
        t_f = _median_time(jf, go.params, x, reps=3 if smoke else 7)
        t_q = _median_time(jq, gq.params, x, reps=3 if smoke else 7)
        row = {
            "app": app, "backend": backend,
            "ms_fp32": t_f * 1e3, "ms_int8": t_q * 1e3,
            "w_bytes_fp32": mem_f["param_bytes"],
            "w_bytes_int8": mem_q["param_bytes"],
            "bytes_ratio": ratio,
            "weight_bytes_saved": mem_q["weight_bytes_saved"],
            "max_err": err,
        }
        record["apps"].append(row)
        print(
            f"quant_app,{app},{backend},{t_f*1e3:.2f},{t_q*1e3:.2f},"
            f"{mem_f['param_bytes']},{mem_q['param_bytes']},{ratio:.2f},{err:.2e}"
        )

    # smoke numbers are CI plumbing, not perf data: never clobber the
    # cross-PR trajectory artifact with them
    default_name = "BENCH_quant_smoke.json" if smoke else "BENCH_quant.json"
    out_path = out_path or os.path.join(RESULTS_DIR, default_name)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"quant,saved,{os.path.abspath(out_path)}")
    return record


# --------------------------------------------------------------------------- #
# conv: implicit-GEMM Pallas kernel + kernel-backend demo-app plans            #
# --------------------------------------------------------------------------- #


def bench_conv(smoke: bool = False, out_path: str | None = None) -> dict:
    from repro.core.graph import compile_plan, optimize
    from repro.models.cnn import APPS, app_masks
    from repro.quant import QTensor

    interpret = kops.interpret_default()
    record: dict = {
        "mode": "interpret" if interpret else "hw",
        "smoke": smoke,
        "kernels": [],
        "apps": [],
    }

    # kernel-level: the implicit-GEMM Pallas conv (all three schemes) vs the
    # XLA lax.conv baseline.  interpret-mode wall-clock measures Python, so
    # shapes stay modest there; parity gates the bench in every mode, the
    # speedup is asserted on real hardware only.  The lax baseline is timed
    # ONCE per shape and shared across the four scheme rows of that shape.
    # The second (full-mode) shape is a wide-channel config whose resident-K
    # workspace overflows the hw VMEM guard: it lowers through the tiled-K
    # contraction path (block_c > 0) instead of falling back to lax.
    shape_list = (
        [(1, 8, 16, 16, 16)] if smoke
        else [(1, 32, 32, 32, 64), (1, 256, 16, 16, 64)]
    )
    key = jax.random.PRNGKey(0)
    reps = 3 if smoke else 7
    print("conv,scheme,NxCxHxW->O,ms_lax,ms_kernel,speedup,max_err")
    for n, c, h, wdt, o in shape_list:
        x = jax.random.normal(key, (n, c, h, wdt)) * 0.5
        w = jax.random.normal(jax.random.PRNGKey(1), (o, c, 3, 3)) * 0.05
        b = jax.random.normal(jax.random.PRNGKey(2), (o,)) * 0.1
        qt = QTensor.from_float(w, axis=0)
        kept = jnp.asarray(np.arange(0, c, 2), jnp.int32)  # half channels live
        x_scale = float(jnp.max(jnp.abs(x))) / 127.0
        base = jax.jit(
            lambda x, w, b: ref.conv2d_ref(x, w, b, stride=1, padding="SAME")
        )
        t_lax = _median_time(base, x, w, b, reps=reps)  # once per shape
        want = base(x, w, b)
        f_dense = jax.jit(lambda x, w, b: kops.conv2d(x, w, b))
        f_chan = jax.jit(lambda x, w, b: kops.conv2d(x, w[:, ::2], b, kept=kept))
        f_w8 = jax.jit(lambda x, v, s, b: kops.conv2d(x, v, b, w_scale=s))
        f_w8a8 = jax.jit(
            lambda x, v, s, b: kops.conv2d(x, v, b, w_scale=s, x_scale=x_scale)
        )
        want_chan = ref.conv2d_ref(jnp.take(x, kept, axis=1), w[:, ::2], b)
        # int8 parity tolerance: a8 rounding noise accumulates over the
        # K = C*kh*kw contraction (~sqrt(K) growth), so the wide-channel
        # shape gets a proportionally wider bound than the 32-channel one
        tol8 = max(5e-2, 5e-2 * (c / 32) ** 0.5)
        cases = (
            ("dense+f32", lambda: f_dense(x, w, b), want, 1e-4),
            ("chanprune+f32", lambda: f_chan(x, w, b), want_chan, 1e-4),
            ("dense+w8", lambda: f_w8(x, qt.values, qt.scale, b), want, tol8),
            ("dense+w8a8", lambda: f_w8a8(x, qt.values, qt.scale, b), want, tol8),
        )
        for scheme, fn, target, tol in cases:
            t_k = _median_time(fn, reps=reps)
            err = float(jnp.abs(fn() - target).max())
            # parity gates the bench in every mode (int8 schemes against the
            # fp32 baseline carry bounded quantization noise)
            assert err <= tol, (scheme, err, tol)
            speedup = t_lax / t_k
            if not interpret:  # interpret timings measure Python, not silicon
                assert speedup > 1.0, (scheme, speedup)
            row = {
                "scheme": scheme, "shape": [n, c, h, wdt, o],
                "ms_lax": t_lax * 1e3, "ms_kernel": t_k * 1e3,
                "speedup": speedup, "max_err": err,
            }
            record["kernels"].append(row)
            print(
                f"conv,{scheme},{n}x{c}x{h}x{wdt}->{o},{t_lax*1e3:.3f},"
                f"{t_k*1e3:.3f},{speedup:.2f},{err:.2e}"
            )

    # app-level acceptance: every conv of the three demo apps lowers through
    # the Pallas kernel (zero fallbacks), at parity with the jnp reference
    # plan, with plan step counts at or below the PR 2 baseline.
    step_caps = {"style_transfer": 33, "coloring": 30, "super_resolution": 37}
    shapes = {
        "style_transfer": (1, 3, 16, 16),
        "coloring": (1, 1, 16, 16),
        "super_resolution": (1, 3, 8, 8),
    }
    print("conv_app,app,steps,convs,fallbacks,ms_reference,ms_kernel,max_err")
    for app in APPS:
        g = APPS[app](key, base=8 if smoke else 16)
        masks, structures = app_masks(g, app, sparsity=0.5)
        go = optimize(g, masks, structures)
        plan_k = compile_plan(go, backend="kernel")
        plan_r = compile_plan(go, backend="reference")
        assert len(plan_k.steps) <= step_caps[app], (app, len(plan_k.steps))
        xa = jax.random.normal(jax.random.PRNGKey(3), shapes[app])
        kops.reset_conv_fallbacks()
        yk = plan_k(go.params, xa)  # eager: fallback counters see every call
        fallbacks = kops.conv_fallback_counts()
        assert not fallbacks, (app, fallbacks)
        err = float(jnp.abs(yk - plan_r(go.params, xa)).max())
        assert err <= 1e-4, (app, err)  # parity gates the bench in every mode
        n_conv = sum(1 for s in plan_k.steps if s.node.op == "conv2d")
        jk = jax.jit(lambda p, x: plan_k(p, x))
        jr = jax.jit(lambda p, x: plan_r(p, x))
        t_r = _median_time(jr, go.params, xa, reps=reps)
        t_k = _median_time(jk, go.params, xa, reps=reps)
        row = {
            "app": app, "plan_steps": len(plan_k.steps), "conv_steps": n_conv,
            "fallbacks": fallbacks, "ms_reference": t_r * 1e3,
            "ms_kernel": t_k * 1e3, "max_err": err,
        }
        record["apps"].append(row)
        print(
            f"conv_app,{app},{len(plan_k.steps)},{n_conv},{fallbacks},"
            f"{t_r*1e3:.2f},{t_k*1e3:.2f},{err:.2e}"
        )

    # smoke numbers are CI plumbing, not perf data: never clobber the
    # cross-PR trajectory artifact with them
    default_name = "BENCH_conv_smoke.json" if smoke else "BENCH_conv.json"
    out_path = out_path or os.path.join(RESULTS_DIR, default_name)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"conv,saved,{os.path.abspath(out_path)}")
    return record


def main(smoke: bool = False):
    if smoke:
        bench_bsr_compute_scaling(k=256, n=256, m=128)
        bench_colcompact_walltime(k=256, n=256, m=64)
        bench_storage(side=256)
        bench_tuned_blocks(shapes=[(8, 128, 128)])
        bench_fusion(smoke=True)
        bench_quant(smoke=True)
        bench_conv(smoke=True)
    else:
        bench_bsr_compute_scaling()
        bench_colcompact_walltime()
        bench_storage()
        bench_tuned_blocks()
        bench_fusion()
        bench_quant()
        bench_conv()


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny shapes (CI, no TPU)")
    ap.add_argument("--repeat", type=int, default=None,
                    help="timed samples per measurement (default 7, 3 in "
                         "smoke); each sample blocks on the result")
    ap.add_argument("--warmup", type=int, default=None,
                    help="discarded warm-up calls before timing (default 1; "
                         "covers JIT compile)")
    cli = ap.parse_args()
    REPEAT, WARMUP = cli.repeat, cli.warmup
    main(smoke=cli.smoke)
