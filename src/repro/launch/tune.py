"""Tuning-cache pre-warm CLI (the ROADMAP's "tuning sweeps on real TPU
hardware" follow-on).

Builds a demo app's graph, runs it through the full pass pipeline, and
executes the resulting plans *eagerly* with tuning enabled, so every kernel
block-size key reachable from the plan -- the ``matmul`` / ``qmatmul`` /
``fused_elementwise`` / ``conv2d`` families -- triggers one candidate sweep
and lands its winner in a JSON :class:`~repro.kernels.ops.TuningCache`.
Ship the JSON to serving via ``REPRO_TUNE_CACHE=path`` and every plan starts
on measured winners instead of seeded defaults.

On real TPU hardware the sweeps time compiled kernels (keys land under
``|hw``); in a CPU container they time interpret-mode Python (``|interpret``)
-- still useful for exercising the full path in CI via ``--smoke``.

Examples::

  PYTHONPATH=src python -m repro.launch.tune --graph-app style_transfer \
      --out results/tuning_style.json
  PYTHONPATH=src python -m repro.launch.tune --graph-app all --quantize \
      --smoke                                   # CI-sized, CPU-safe
  PYTHONPATH=src python -m repro.launch.tune --graph-app coloring \
      --ops conv2d,qmatmul --smoke              # sweep only two key families
"""

from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..utils.compile_cache import enable_compile_cache


def _sweep_app(app: str, args) -> None:
    """Compile ``app`` and execute its plan(s) eagerly so every reachable
    kernel call resolves -- and therefore sweeps -- its tuning key."""
    from ..core.graph import PassContext, PassManager, compile_plan
    from ..models.cnn import APP_ACT_SKIP, APP_QUANT_SKIP, APPS, app_masks
    from ..quant import calibrate_plan

    g = APPS[app](jax.random.PRNGKey(args.seed), base=args.base)
    masks, structures = app_masks(g, app, sparsity=args.sparsity)
    go = PassManager().run(g, PassContext(masks=masks, structures=structures))
    c_in = 1 if app == "coloring" else 3
    shape = (args.batch, c_in, args.size, args.size)
    rng = np.random.default_rng(args.seed)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    plan = compile_plan(go, backend="kernel")
    jax.block_until_ready(plan(go.params, x))  # f32 matmul/conv/ew keys
    n_keys = len(kops.tuning_cache().entries)
    print(f"{app}: kernel plan swept ({len(plan.steps)} steps, "
          f"{n_keys} cache keys so far)")

    if args.quantize:
        plan_ref = compile_plan(go, backend="reference")
        table = calibrate_plan(plan_ref, go.params, [x])
        gq = PassManager(("quantize",)).run(
            go,
            PassContext(
                calibration=table, quant_skip=APP_QUANT_SKIP[app],
                act_quant_skip=APP_ACT_SKIP[app],
            ),
        )
        plan_q = compile_plan(gq, backend="quant")
        jax.block_until_ready(plan_q(gq.params, x))  # qmatmul/int8-conv keys
        print(f"{app}: quant plan swept "
              f"({len(kops.tuning_cache().entries)} cache keys so far)")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--graph-app",
                    choices=["style_transfer", "coloring", "super_resolution", "all"],
                    default="all", help="demo app whose plan keys to pre-warm")
    ap.add_argument("--size", type=int, default=64, help="frame size")
    ap.add_argument("--base", type=int, default=16, help="channel width")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quantize", action="store_true",
                    help="also sweep the INT8 plan (qmatmul / int8 conv keys)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CPU/CI (sweeps interpret-mode keys)")
    ap.add_argument("--ops", default=None,
                    help="comma-separated key families to sweep (e.g. "
                         "'conv2d,qmatmul'); other families resolve to "
                         "defaults without sweeping")
    ap.add_argument("--out", default=None,
                    help="cache JSON path (default: REPRO_TUNE_CACHE or "
                         "results/tuning_cache.json)")
    args = ap.parse_args()
    if args.smoke:
        args.size, args.base = min(args.size, 16), min(args.base, 8)

    cache = kops.tuning_cache()
    cache.enabled = True
    if args.ops:
        cache.ops_filter = frozenset(
            op.strip() for op in args.ops.split(",") if op.strip()
        )
    apps = (
        ["style_transfer", "coloring", "super_resolution"]
        if args.graph_app == "all" else [args.graph_app]
    )
    for app in apps:
        _sweep_app(app, args)

    print(cache.report())
    print(cache.stats_report())
    out = args.out or os.environ.get("REPRO_TUNE_CACHE") or os.path.join(
        "results", "tuning_cache.json"
    )
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    print(f"tune: {cache.sweeps} sweeps, {len(cache.entries)} keys -> {cache.save(out)}")


if __name__ == "__main__":
    main()
