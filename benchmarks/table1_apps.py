"""Table 1 analogue: the paper's three apps under
{unpruned, pruned, pruned+compiler} on this host's XLA-CPU.

The paper measured ms/frame on a Galaxy S10 (Adreno 640); we measure the same
three-way contrast on CPU-XLA (absolute numbers differ; the *shape* of the
table -- monotone speedups from pruning and again from the compiler passes --
is the reproduction target).  FLOP counts come from XLA cost analysis of the
lowered graphs, so the compiler claim is hardware-independent.

Paper Table 1 (ms):     style 283/178/67   coloring 137/85/38   SR 269/192/73
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import compile_plan, lower, optimize
from repro.core.graph.ir import Graph
from repro.models.cnn import (  # noqa: F401  (re-exported for tests/scripts)
    APPS,
    PAPER_RECIPE,
    PAPER_TABLE1,
    _channel_mask,
    _pattern_mask,
    app_masks,
)
from repro.utils.compile_cache import enable_compile_cache

INPUT_SHAPES = {
    "style_transfer": (1, 3, 128, 128),
    "coloring": (1, 1, 128, 128),
    "super_resolution": (1, 3, 96, 96),
}

#: ``--smoke`` (make bench-smoke): tiny frames so CI exercises the full
#: measurement path -- the numbers are not meaningful at this scale
SMOKE_SHAPES = {
    "style_transfer": (1, 3, 32, 32),
    "coloring": (1, 1, 32, 32),
    "super_resolution": (1, 3, 16, 16),
}


# --------------------------------------------------------------------------- #
# measurement                                                                  #
# --------------------------------------------------------------------------- #


def count_graph_flops(g: Graph, x_shape: Tuple[int, ...]) -> float:
    fn = lower(g, use_kernels=False)
    x = jax.ShapeDtypeStruct(x_shape, jnp.float32)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), g.params)
    lowered = jax.jit(fn).lower(params, x)
    return float(lowered.compile().cost_analysis().get("flops", 0.0))


def graph_param_bytes(g: Graph) -> int:
    return int(sum(np.asarray(v).nbytes for v in jax.tree.leaves(g.params)))


def _time_call(fn, *args, reps: int = 5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_app(
    app: str, sparsity: float = 0.5, base: int = 32, reps: int = 5,
    shapes: Dict[str, Tuple[int, ...]] = INPUT_SHAPES,
) -> Dict[str, Dict]:
    g = APPS[app](jax.random.PRNGKey(0), base=base)
    x = jax.random.normal(jax.random.PRNGKey(1), shapes[app], jnp.float32)

    # 1) unpruned
    f_dense = jax.jit(lower(g, use_kernels=False))
    t_dense = _time_call(f_dense, g.params, x, reps=reps)

    # 2) pruned (masked dense: ADMM output before any compiler work)
    masks, structures = app_masks(g, app, sparsity)
    pm = {
        k: ({**v, "w": v["w"] * masks[k]} if k in masks else v)
        for k, v in g.params.items()
    }
    t_pruned = _time_call(f_dense, pm, x, reps=reps)

    # 3) pruned + compiler (PassManager pipeline -> execution plan)
    go = optimize(g, masks, structures)
    plan = compile_plan(go, backend="reference")
    f_opt = jax.jit(plan)
    t_opt = _time_call(f_opt, go.params, x, reps=reps)
    mem = plan.memory_estimate(jax.ShapeDtypeStruct(shapes[app], jnp.float32))

    flops = {
        "unpruned": count_graph_flops(g, shapes[app]),
        "pruned_compiler": count_graph_flops(go, shapes[app]),
    }
    bytes_ = {"unpruned": graph_param_bytes(g), "pruned_compiler": graph_param_bytes(go)}
    # numerical agreement between pruned and pruned+compiler
    err = float(jnp.abs(f_dense(pm, x) - f_opt(go.params, x)).max())
    return {
        "ms": {"unpruned": t_dense * 1e3, "pruned": t_pruned * 1e3, "pruned_compiler": t_opt * 1e3},
        "flops": flops,
        "param_bytes": bytes_,
        "agreement_max_err": err,
        "paper_ms": PAPER_TABLE1[app],
        "plan_steps": len(plan.steps),
        "peak_activation_bytes": mem["peak_activation_bytes"],
    }


def main(smoke: bool = False) -> None:
    print("app,variant,ms_per_frame,flops,param_bytes,paper_ms")
    for app in APPS:
        r = (
            bench_app(app, base=8, reps=2, shapes=SMOKE_SHAPES)
            if smoke
            else bench_app(app)
        )
        for variant in ("unpruned", "pruned", "pruned_compiler"):
            print(
                f"{app},{variant},{r['ms'][variant]:.2f},"
                f"{r['flops'].get(variant if variant != 'pruned' else 'unpruned', 0):.3e},"
                f"{r['param_bytes'].get(variant if variant != 'pruned' else 'unpruned', 0)},"
                f"{r['paper_ms'][variant]}"
            )
        sp = r["ms"]["unpruned"] / r["ms"]["pruned_compiler"]
        psp = r["paper_ms"]["unpruned"] / r["paper_ms"]["pruned_compiler"]
        print(
            f"# {app}: ours {sp:.2f}x end-to-end (paper {psp:.2f}x); "
            f"flop cut {r['flops']['unpruned'] / max(r['flops']['pruned_compiler'],1):.2f}x; "
            f"agreement {r['agreement_max_err']:.2e}; "
            f"plan {r['plan_steps']} steps, peak act {r['peak_activation_bytes']/1e6:.2f} MB"
        )


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny shapes (CI, no TPU)")
    main(smoke=ap.parse_args().smoke)
