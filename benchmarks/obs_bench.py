"""Observability overhead benchmark: tracing must be (nearly) free.

The telemetry contract (ARCHITECTURE.md section 8) promises two ceilings,
both gated here against a **bare-loop baseline** -- a re-implementation of
the executor's untraced step loop with ZERO obs code in it (no ``enabled()``
branch, no argument validation, no observer checks), so the measured ratios
charge the instrumentation for everything it adds:

1. **disabled-mode <= 1%** -- with tracing off, ``plan(params, x)`` may cost
   at most 1% over the bare loop.  The disabled path is one module-flag
   check per run plus the shared stateless ``NULL_SPAN`` -- this gate is
   what keeps per-step spans out of the hot loop when nobody is looking.
2. **traced-mode <= 5%** -- with a tracing session armed, the full per-step
   span machinery (one ``cat="plan"`` span + one ``cat="step"`` span per
   step, out-shape annotation included) may cost at most 5% end-to-end on
   the eager reference plans.

Timing discipline: the three variants are interleaved round-robin (so a
frequency-scaling drift hits all of them equally) and each is scored by its
**min over reps** -- the noise-robust statistic for lower-bounded wall-clock.
Because a 1% gate on millisecond-scale Python loops still flakes under CI
jitter, each app gets up to ``--attempts`` independent measurement rounds
and keeps its best (lowest-overhead) round; the gate fails only if every
attempt missed.  Also recorded: registry exporter sizes + snapshot cost for
a serving-shaped registry, and a profiler self-check.

Writes ``results/BENCH_obs.json`` (``--smoke``: ``BENCH_obs_smoke.json``,
wired into ``make bench-smoke``); gates feed the cross-PR floors in
``benchmarks/trajectory.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import compile_plan
from repro.models.cnn import APPS
from repro.obs import metrics, profile_plan, trace
from repro.utils.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")

DISABLED_CEIL = 1.01  # disabled-mode overhead vs bare loop
TRACED_CEIL = 1.05  # traced-mode overhead vs bare loop


def _bare_runner(plan):
    """The executor's untraced step loop with all obs/validation stripped:
    the honest baseline the instrumentation is charged against."""
    handlers, rt = plan._handlers, plan._rt
    steps, inputs, outputs = plan.steps, plan.graph.inputs, plan.graph.outputs

    def run(params, *args):
        env = dict(zip(inputs, args))
        for step in steps:
            n = step.node
            xs = [env[i] for i in n.inputs]
            env[n.name] = handlers[n.op](params.get(n.name, {}), xs, n.attrs, rt)
            for f in step.frees:
                del env[f]
        outs = tuple(env[o] for o in outputs)
        return outs[0] if len(outs) == 1 else outs

    return run


def _measure_once(plan, params, x, reps: int) -> dict:
    """One interleaved round: min-of-reps wall ms for bare / disabled /
    traced, plus the traced run's event count."""
    bare = _bare_runner(plan)
    assert not trace.enabled()
    # warm every variant (jit caches, allocator) outside the timed window
    jax.block_until_ready(bare(params, x))
    jax.block_until_ready(plan(params, x))
    with trace.tracing():
        jax.block_until_ready(plan(params, x))
    t = {"bare": [], "disabled": [], "traced": []}
    events_per_run = 0
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(bare(params, x))
        t["bare"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(plan(params, x))
        t["disabled"].append(time.perf_counter() - t0)
        with trace.tracing() as buf:
            t0 = time.perf_counter()
            jax.block_until_ready(plan(params, x))
            t["traced"].append(time.perf_counter() - t0)
        events_per_run = len(buf)
    ms = {k: float(np.min(v)) * 1e3 for k, v in t.items()}
    return {
        "bare_ms": ms["bare"],
        "disabled_ms": ms["disabled"],
        "traced_ms": ms["traced"],
        "disabled_overhead": ms["disabled"] / ms["bare"],
        "traced_overhead": ms["traced"] / ms["bare"],
        "events_per_run": events_per_run,
    }


def bench_obs(smoke: bool = False, out_path: str | None = None,
              attempts: int = 5) -> dict:
    record: dict = {
        "mode": "interpret",  # eager reference plans: wall-clock is Python
        "smoke": smoke,
        "ceilings": {"disabled": DISABLED_CEIL, "traced": TRACED_CEIL},
        "overhead": [],
        "registry": {},
        "profiler": {},
    }
    base, size = (8, 12) if smoke else (16, 24)
    reps = 20 if smoke else 40
    rng = np.random.default_rng(0)

    # 1. per-app overhead gates (best-of-attempts; see module docstring)
    print("obs_overhead,app,bare_ms,disabled_ms,traced_ms,"
          "disabled_ovh,traced_ovh,attempts")
    for app in APPS:
        g = APPS[app](jax.random.PRNGKey(0), base=base)
        plan = compile_plan(g, backend="reference")
        c = 1 if app == "coloring" else 3
        x = jnp.asarray(rng.standard_normal((1, c, size, size)), jnp.float32)
        best = None
        for attempt in range(1, attempts + 1):
            m = _measure_once(plan, g.params, x, reps)
            if best is None or (
                max(m["disabled_overhead"] - DISABLED_CEIL,
                    m["traced_overhead"] - TRACED_CEIL)
                < max(best["disabled_overhead"] - DISABLED_CEIL,
                      best["traced_overhead"] - TRACED_CEIL)
            ):
                best = m
            if (best["disabled_overhead"] <= DISABLED_CEIL
                    and best["traced_overhead"] <= TRACED_CEIL):
                break
        row = {"app": app, "steps": len(plan.steps),
               "attempts": attempt, **best}
        record["overhead"].append(row)
        print(f"obs_overhead,{app},{row['bare_ms']:.3f},"
              f"{row['disabled_ms']:.3f},{row['traced_ms']:.3f},"
              f"{row['disabled_overhead']:.4f},{row['traced_overhead']:.4f},"
              f"{attempt}")
        assert row["disabled_overhead"] <= DISABLED_CEIL, row
        assert row["traced_overhead"] <= TRACED_CEIL, row
        # traced run really traced: plan span + one span per step, paired
        assert row["events_per_run"] == 2 * (len(plan.steps) + 1), row

    # 2. registry exporter cost on a serving-shaped registry
    reg = metrics.MetricsRegistry()
    n_series = 30 if smoke else 120
    for i in range(n_series):
        reg.counter("bench_events_total", plan=f"p{i % 8}", event=f"e{i}").inc(i)
        h = reg.histogram("bench_latency_seconds", plan=f"p{i % 8}")
        h.observe(0.001 * (i + 1))
    t0 = time.perf_counter()
    snap = reg.snapshot()
    snap_us = (time.perf_counter() - t0) * 1e6
    record["registry"] = {
        "series": n_series,
        "snapshot_us": snap_us,
        "json_bytes": len(reg.to_json()),
        "prometheus_bytes": len(reg.to_prometheus()),
        "families": len(snap),
    }
    print(f"obs_registry,series={n_series},snapshot_us={snap_us:.1f},"
          f"json_bytes={record['registry']['json_bytes']},"
          f"prom_bytes={record['registry']['prometheus_bytes']}")

    # 3. profiler self-check: rows == steps, shares sum to 100%
    app = "style_transfer"
    g = APPS[app](jax.random.PRNGKey(0), base=base)
    plan = compile_plan(g, backend="reference")
    x = jnp.asarray(rng.standard_normal((1, 3, size, size)), jnp.float32)
    prof = profile_plan(plan, g.params, x, runs=2, warmup=1)
    pct_sum = float(sum(s.pct for s in prof.steps))
    record["profiler"] = {
        "app": app,
        "rows": len(prof.steps),
        "steps": len(plan.steps),
        "total_ms": prof.total_ms,
        "pct_sum": pct_sum,
        "trace_events": len(prof.trace),
    }
    assert len(prof.steps) == len(plan.steps)
    assert abs(pct_sum - 100.0) < 1e-6
    print(f"obs_profiler,{app},rows={len(prof.steps)},"
          f"total_ms={prof.total_ms:.2f}")

    default_name = "BENCH_obs_smoke.json" if smoke else "BENCH_obs.json"
    out_path = out_path or os.path.join(RESULTS_DIR, default_name)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"obs,saved,{os.path.abspath(out_path)}")
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny shapes (CI)")
    ap.add_argument("--attempts", type=int, default=5,
                    help="measurement rounds per app; keep the best")
    args = ap.parse_args()
    bench_obs(smoke=args.smoke, attempts=args.attempts)
