"""Plan profiler launcher: where does the millisecond go, per step.

Compiles one of the paper's demo apps through the full pipeline (masks ->
PassManager -> execution plan, optionally calibrated + quantized to INT8),
runs it under tracing via :func:`repro.obs.profile.profile_plan`, and
prints the per-step cost table -- wall ms, share of total, estimated bytes
moved, kernel-vs-reference attribution.

Examples (CPU)::

  PYTHONPATH=src python -m repro.launch.profile --graph-app style_transfer \
      --trace-out trace.json             # Chrome-trace JSON for Perfetto
  PYTHONPATH=src python -m repro.launch.profile --graph-app coloring \
      --quantize --runs 5 --json-out profile.json
  PYTHONPATH=src python -m repro.launch.profile --graph-app super_resolution \
      --backend guarded --top 10

Load ``--trace-out`` files at https://ui.perfetto.dev (or
``chrome://tracing``): one ``cat="plan"`` span per run, one ``cat="step"``
span per plan step nested under it.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def build_app_plan(args):
    """The shared demo-app build path (same pipeline as launch/serve.py):
    returns ``(plan, params, input_shape)`` for ``args.graph_app``."""
    from ..core.graph import PassContext, PassManager, compile_plan
    from ..models.cnn import APP_ACT_SKIP, APP_QUANT_SKIP, APPS, app_masks

    g = APPS[args.graph_app](jax.random.PRNGKey(args.seed), base=args.base)
    masks, structures = app_masks(g, args.graph_app, sparsity=args.sparsity)
    go = PassManager().run(g, PassContext(masks=masks, structures=structures))

    on_tpu = jax.default_backend() == "tpu"
    backend = args.backend or ("kernel" if on_tpu else "reference")
    c_in = 1 if args.graph_app == "coloring" else 3
    shape = (args.batch, c_in, args.size, args.size)
    rng = np.random.default_rng(args.seed)

    if args.quantize:
        from ..quant import calibrate_plan

        plan_f32 = compile_plan(go, backend="reference")
        batches = [
            jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(args.calib_batches)
        ]
        table = calibrate_plan(plan_f32, go.params, batches)
        qctx = PassContext(
            calibration=table, quant_skip=APP_QUANT_SKIP[args.graph_app],
            act_quant_skip=APP_ACT_SKIP[args.graph_app],
        )
        go = PassManager(("quantize",)).run(go, qctx)
        if args.backend is None:
            backend = "quant" if on_tpu else "reference"
    plan = compile_plan(go, backend=backend)
    return plan, go.params, shape


def main() -> None:
    from ..obs import profile_plan
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--graph-app",
                    choices=["style_transfer", "coloring", "super_resolution"],
                    required=True, help="demo app to profile")
    ap.add_argument("--quantize", action="store_true",
                    help="calibrate + quantize the plan to INT8 first")
    ap.add_argument("--backend", default=None,
                    choices=["kernel", "reference", "quant", "guarded"],
                    help="override the auto backend (kernel on TPU, "
                         "reference elsewhere)")
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--size", type=int, default=64, help="frame size")
    ap.add_argument("--base", type=int, default=16, help="channel width")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--runs", type=int, default=3,
                    help="traced executions; per-step ms is their median")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--top", type=int, default=None,
                    help="print only the N hottest steps")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calib-batches", type=int, default=2)
    ap.add_argument("--trace-out", default=None,
                    help="write the (last traced run's) Chrome-trace JSON "
                         "here -- loadable in Perfetto / chrome://tracing")
    ap.add_argument("--json-out", default=None,
                    help="write the per-step profile table as JSON here")
    args = ap.parse_args()

    plan, params, shape = build_app_plan(args)
    x = jnp.asarray(
        np.random.default_rng(args.seed).standard_normal(shape), jnp.float32
    )
    prof = profile_plan(plan, params, x, runs=args.runs, warmup=args.warmup)
    print(f"{args.graph_app}: {shape[0]}x{shape[2]}x{shape[3]} "
          f"sparsity={args.sparsity} quantize={args.quantize}")
    print(prof.render_text(top=args.top))
    mem = prof.memory
    print(f"memory: peak_act={mem['peak_activation_bytes'] / 1e6:.2f}MB "
          f"params={mem['param_bytes'] / 1e6:.2f}MB "
          f"saved={mem['weight_bytes_saved'] / 1e6:.2f}MB")
    if args.trace_out:
        print(f"trace: {prof.trace.save(args.trace_out)} "
              f"({len(prof.trace.events)} events; load in Perfetto)")
    if args.json_out:
        print(f"profile json: {prof.save_json(args.json_out)}")


if __name__ == "__main__":
    main()
