"""Serving-layer benchmark: the async continuous-batching engine
(`repro/serving/scheduler.py`) hosting all three demo apps in one process.

What is recorded (``results/BENCH_serving.json``, ``_smoke`` variant in CI):

1. **parity** -- the async path must be bit-close to direct
   ``ExecutionPlan`` execution for every app (padding, batching and the
   scheduler must be invisible in the outputs); gated in EVERY mode.
2. **sustained throughput** -- mixed traffic over the three apps through
   the background scheduler thread: requests/s, p50/p95/p99 request
   latency, padding overhead (padded frames per executed slot) and the
   deadline-miss rate.  The speedup vs serial single-frame execution is
   asserted on real hardware only (interpret/CPU wall-clock measures
   Python, not the schedule).
3. **backpressure** -- bounded admission queues under flood: the reject
   policy's rejection count and the shed policy's evictions, both of which
   must actually trigger (the queue bound is load-bearing).
4. **fairness** -- 10:1 skewed traffic over two plans: the minority plan's
   requests must complete in the first scheduler rotations, not behind the
   majority's backlog.
5. **multi_tenant** -- sustained overload at 2x capacity with a 10:1
   hot/light tenant skew on the injected clock: per-tenant p50/p95/p99,
   throttle/shed counts and ladder-transition counts.  Gated: the in-quota
   light tenant loses zero requests and stays within its deadline SLO
   while the hot tenant's excess is absorbed by its quota + degradation
   ladder -- the armed watchdog must never fire.

``--smoke`` shrinks shapes and traffic so CI exercises the full path
without a TPU (wired into ``make bench-smoke``).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import compile_plan, optimize
from repro.kernels import ops as kops
from repro.models.cnn import APPS, app_masks
from repro.serving import AsyncPlanServer, QueueFullError
from repro.utils.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")

APP_FRAME_SHAPES = {
    "style_transfer": (3, 16, 16),
    "coloring": (1, 16, 16),
    "super_resolution": (3, 8, 8),
}


def _build_plans(smoke: bool, backend: str):
    plans = {}
    for app in APPS:
        g = APPS[app](jax.random.PRNGKey(0), base=8 if smoke else 16)
        masks, structures = app_masks(g, app, sparsity=0.5)
        go = optimize(g, masks, structures)
        plans[app] = (compile_plan(go, backend=backend), go.params)
    return plans


def _frame(rng, app):
    return jnp.asarray(rng.standard_normal(APP_FRAME_SHAPES[app]), jnp.float32)


def _latency_pcts(lats) -> dict:
    arr = np.asarray([v for v in lats if v is not None])
    if not arr.size:
        return {"count": 0, "p50": 0.0, "p95": 0.0, "p99": 0.0, "mean": 0.0}
    return {
        "count": int(arr.size),
        "p50": float(np.percentile(arr, 50)),
        "p95": float(np.percentile(arr, 95)),
        "p99": float(np.percentile(arr, 99)),
        "mean": float(arr.mean()),
    }


def bench_serving(smoke: bool = False, out_path: str | None = None) -> dict:
    interpret = kops.interpret_default()
    backend = "reference" if interpret else "kernel"
    record: dict = {
        "mode": "interpret" if interpret else "hw",
        "smoke": smoke,
        "backend": backend,
        "parity": [],
        "throughput": {},
        "backpressure": {},
        "fairness": {},
        "multi_tenant": {},
    }
    plans = _build_plans(smoke, backend)
    rng = np.random.default_rng(0)
    batch_size = 4

    # 1. parity: deterministic (step-driven) async serving vs direct plan
    # execution -- gates the bench in every mode.
    print("serving_parity,app,requests,max_err")
    now = [0.0]
    server = AsyncPlanServer(flush_after=1.0, clock=lambda: now[0])
    for app, (plan, params) in plans.items():
        server.add_plan(app, plan, params, batch_size)
    probes = {
        app: [(_frame(rng, app), None) for _ in range(batch_size + 1)]
        for app in plans
    }
    for app, frames in probes.items():
        probes[app] = [(x, server.submit(app, x)) for x, _ in frames]
    while server.step(force=True):
        pass
    for app, frames in probes.items():
        plan, params = plans[app]
        want = plan(params, jnp.stack([x for x, _ in frames]))
        err = float(
            max(
                jnp.max(jnp.abs(jnp.asarray(h.result(0)) - jnp.asarray(want)[i]))
                for i, (_, h) in enumerate(frames)
            )
        )
        assert err <= 1e-5, (app, err)  # parity gates the bench in every mode
        record["parity"].append({"app": app, "requests": len(frames), "max_err": err})
        print(f"serving_parity,{app},{len(frames)},{err:.2e}")
    server.close()

    # 2. sustained throughput through the scheduler thread: mixed traffic,
    # per-request deadlines, latency percentiles, padding overhead.
    n_requests = 24 if smoke else 240
    deadline = 5.0 if smoke else 1.0
    apps = list(plans)
    server = AsyncPlanServer(flush_after=0.005, tick_interval=0.001)
    for app, (plan, params) in plans.items():
        server.add_plan(app, plan, params, batch_size)
    with server:
        server.start()
        for app in apps:  # warm chunk compilation out of the timed window
            server.submit(app, jnp.zeros(APP_FRAME_SHAPES[app], jnp.float32)).result()
        warm_stats = server.stats
        t0 = time.perf_counter()
        handles = []
        for i in range(n_requests):
            app = apps[i % len(apps)]
            handles.append(
                server.submit(app, _frame(rng, app), priority=i % 2, deadline=deadline)
            )
        for h in handles:
            h.result()
        dt = time.perf_counter() - t0
        s = server.stats
        # percentiles over the traffic handles only: the server's reservoirs
        # also hold the warmup requests, whose latency is jit compile time
        lat = _latency_pcts([h.latency for h in handles])
        batches = s["batches"] - warm_stats["batches"]
        padded = s["padded_frames"] - warm_stats["padded_frames"]
        misses = s["deadline_misses"] - warm_stats["deadline_misses"]
        record["throughput"] = {
            "requests": n_requests,
            "wall_s": dt,
            "req_per_s": n_requests / dt,
            "batches": batches,
            "padded_frames": padded,
            "padding_overhead": padded / max(batches * batch_size, 1),
            "deadline_misses": misses,
            "deadline_miss_rate": misses / n_requests,
            "deadline_flushes": s["deadline_flushes"] - warm_stats["deadline_flushes"],
            "latency_s": lat,
            "per_plan_latency_s": {
                a: _latency_pcts([h.latency for h in handles if h.plan == a])
                for a in apps
            },
        }

    # serial single-frame baseline over the same traffic volume: the
    # throughput the batching schedule must beat on real hardware
    serial_fns = {
        app: jax.jit(lambda p, x, _plan=plan: _plan(p, x))
        for app, (plan, params) in plans.items()
    }
    for app, (plan, params) in plans.items():  # compile outside the window
        jax.block_until_ready(serial_fns[app](params, jnp.zeros((1, *APP_FRAME_SHAPES[app]))))
    t0 = time.perf_counter()
    for i in range(n_requests):
        app = apps[i % len(apps)]
        jax.block_until_ready(serial_fns[app](plans[app][1], _frame(rng, app)[None]))
    serial_dt = time.perf_counter() - t0
    record["throughput"]["serial_req_per_s"] = n_requests / serial_dt
    speedup = serial_dt / record["throughput"]["wall_s"]
    record["throughput"]["speedup_vs_serial"] = speedup
    if not interpret:  # interpret/CPU wall-clock measures Python, not silicon
        assert speedup > 1.0, speedup
    t = record["throughput"]
    print(
        f"serving_throughput,{n_requests},{t['req_per_s']:.1f}req/s,"
        f"p50={t['latency_s']['p50'] * 1e3:.2f}ms,"
        f"p95={t['latency_s']['p95'] * 1e3:.2f}ms,"
        f"p99={t['latency_s']['p99'] * 1e3:.2f}ms,"
        f"pad={t['padding_overhead']:.3f},miss={t['deadline_miss_rate']:.3f},"
        f"vs_serial={speedup:.2f}x"
    )

    # 3. backpressure: both overload policies must actually trigger.
    app = apps[0]
    plan, params = plans[app]
    for policy in ("reject", "shed"):
        server = AsyncPlanServer(max_queue=4, overload=policy, clock=lambda: 0.0)
        server.add_plan(app, plan, params, batch_size)
        rejected = 0
        handles = []
        # 3 over the bound; the overflow submits carry a higher priority so
        # the shed policy actually evicts queued work (an equal-priority
        # newcomer is itself the victim and raises, like reject)
        for i in range(7):
            try:
                handles.append(
                    server.submit(app, _frame(rng, app), priority=int(i >= 4))
                )
            except QueueFullError:
                rejected += 1
        failed = sum(1 for h in handles if h.done() and h.exception() is not None)
        server.close()
        s = server.stats
        row = {"policy": policy, "submitted": 7, "max_queue": 4,
               "rejected": s["rejected"], "shed": s["shed"]}
        record["backpressure"][policy] = row
        assert (s["rejected"] if policy == "reject" else s["shed"]) == 3, row
        assert (rejected if policy == "reject" else failed) == 3, row
        print(f"serving_backpressure,{policy},rejected={s['rejected']},shed={s['shed']}")

    # 4. fairness under 10:1 skew: the minority plan's batch must execute in
    # the first scheduler rotations, not after the majority's backlog.
    heavy, light = apps[0], apps[1]
    server = AsyncPlanServer(clock=lambda: 0.0)
    for a in (heavy, light):
        server.add_plan(a, *plans[a], batch_size=batch_size)
    heavy_handles = [server.submit(heavy, _frame(rng, heavy)) for _ in range(10 * batch_size)]
    light_handles = [server.submit(light, _frame(rng, light)) for _ in range(batch_size)]
    ticks_to_light = 0
    while not all(h.done() for h in light_handles):
        server.step()
        ticks_to_light += 1
    heavy_done = sum(h.done() for h in heavy_handles)
    server.close()
    record["fairness"] = {
        "heavy_requests": len(heavy_handles), "light_requests": len(light_handles),
        "ticks_until_light_done": ticks_to_light,
        "heavy_done_at_that_point": heavy_done,
    }
    assert ticks_to_light <= 2, ticks_to_light  # round-robin, not FIFO-global
    print(f"serving_fairness,ticks_until_light_done={ticks_to_light},"
          f"heavy_done={heavy_done}/{len(heavy_handles)}")

    # 5. multi-tenant overload: 2x sustained capacity with a 10:1 hot/light
    # skew, driven tick-by-tick on the injected clock (deterministic).  The
    # in-quota light tenant must ride out the storm -- zero lost requests,
    # deadline misses within its SLO -- while the hot tenant's excess is
    # absorbed by its token bucket and the degradation ladder (throttle ->
    # shrink_flush -> demote -> shed).  The watchdog is armed and must never
    # fire: overload is a policy decision here, not a hang.
    from repro.serving import LadderConfig, QuotaExceededError, TenantSLO

    app = apps[0]
    plan, params = plans[app]
    now = [0.0]
    dt = 0.01  # one scheduler tick = one batch of service capacity
    ticks = 60 if smoke else 240
    deadline_s = 10 * dt
    server = AsyncPlanServer(
        clock=lambda: now[0], overload="shed", max_queue=512,
        deadline_margin=2 * dt, watchdog=30.0,
    )
    server.add_plan(app, plan, params, batch_size)
    server.register_variant(app, "cheap", plan, params)
    server.add_tenant(
        "hot", weight=1.0, rate=6.0 / dt, burst=2.0 * batch_size,
        slo=TenantSLO(p99_latency=5 * dt, min_samples=4),
        ladder=LadderConfig(interval=5 * dt, breach_evals=1,
                            recover_evals=4, shed_below_priority=1),
    )
    server.add_tenant("light", weight=1.0)
    handles = {"hot": [], "light": []}
    turned_away = {"hot": 0, "light": 0}
    throttled_at_submit = 0
    arrival = 0
    for _ in range(ticks):
        for _ in range(2 * batch_size):  # 2x capacity offered per tick
            tenant = "light" if arrival % 11 == 0 else "hot"  # 10:1 skew
            arrival += 1
            try:
                handles[tenant].append(server.submit(
                    app, _frame(rng, app),
                    priority=1 if tenant == "light" else 0,
                    deadline=deadline_s, tenant=tenant,
                ))
            except QuotaExceededError:
                turned_away[tenant] += 1
                throttled_at_submit += 1
            except QueueFullError:  # ladder shed or queue shed
                turned_away[tenant] += 1
        now[0] += dt
        server.step()
    while server.pending():  # drain the residual backlog on the same clock
        now[0] += dt
        server.step(force=True)
    per_tenant = server.stats["per_tenant"]
    plan_stats = server.stats["per_plan"][app]
    tenant_health = server.health()["tenants"]
    server.close()

    def tenant_row(name):
        hs = handles[name]
        ok = [h for h in hs if h.exception() is None]
        misses = sum(h.deadline_missed for h in ok)
        ts = per_tenant[name]
        return {
            "offered": len(hs) + turned_away[name],
            "admitted": len(hs),
            "lost": len(hs) - len(ok),  # admitted but never completed
            "turned_away": turned_away[name],
            "throttled": ts["throttled"],
            "ladder_shed": ts["ladder_shed"],
            "demoted_admissions": ts["demoted_admissions"],
            "ladder_up": ts["ladder_up"],
            "ladder_down": ts["ladder_down"],
            "ladder_level": tenant_health[name]["level_name"],
            "deadline_misses": misses,
            "deadline_miss_rate": misses / max(len(ok), 1),
            "latency_s": _latency_pcts([h.latency for h in ok]),
        }

    hot, light = tenant_row("hot"), tenant_row("light")
    record["multi_tenant"] = {
        "ticks": ticks, "capacity_per_tick": batch_size,
        "offered_per_tick": 2 * batch_size, "skew": "10:1",
        "deadline_s": deadline_s, "hot": hot, "light": light,
        "queue_shed": plan_stats["shed"],
        "watchdog_timeouts": plan_stats["watchdog_timeouts"],
    }
    # the overload gate: in-SLO tenant unharmed, ladder (not watchdog)
    # absorbed the excess, and every transition is registry-visible
    assert light["lost"] == 0 and light["turned_away"] == 0, light
    assert light["deadline_miss_rate"] <= 0.1, light
    assert hot["ladder_up"] >= 1, hot  # the ladder actually engaged
    assert hot["ladder_shed"] + hot["throttled"] >= 1, hot
    assert plan_stats["watchdog_timeouts"] == 0
    from repro.obs import metrics as _metrics

    transitions = _metrics.registry().label_counts(
        "serving_ladder_transitions_total", "tenant", "direction"
    )
    assert sum(transitions.values()) >= hot["ladder_up"], transitions
    print(
        f"serving_multi_tenant,hot,p99={hot['latency_s']['p99'] * 1e3:.1f}ms,"
        f"throttled={hot['throttled']},ladder_shed={hot['ladder_shed']},"
        f"ladder_up={hot['ladder_up']},level={hot['ladder_level']}"
    )
    print(
        f"serving_multi_tenant,light,p99={light['latency_s']['p99'] * 1e3:.1f}ms,"
        f"miss_rate={light['deadline_miss_rate']:.3f},lost={light['lost']},"
        f"watchdog_timeouts={plan_stats['watchdog_timeouts']}"
    )

    # smoke numbers are CI plumbing, not perf data: never clobber the
    # cross-PR trajectory artifact with them
    default_name = "BENCH_serving_smoke.json" if smoke else "BENCH_serving.json"
    out_path = out_path or os.path.join(RESULTS_DIR, default_name)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"serving,saved,{os.path.abspath(out_path)}")
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny shapes (CI, no TPU)")
    bench_serving(smoke=ap.parse_args().smoke)
