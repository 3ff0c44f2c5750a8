"""Robustness benchmark: what guarded degradation costs and what chaos
cannot break.

What is recorded (``results/BENCH_robustness.json``, ``_smoke`` variant in
CI):

1. **degraded** -- per demo app, the eager reference plan vs the guarded
   plan forced into full degradation (a 100% injected kernel-failure rate,
   so every step demotes through the breaker machinery): the degraded-mode
   overhead ratio is the price of the guard rails when everything is on
   fire, and the outputs must be *bit-identical* to the reference plan
   (the fallback is the oracle).  The clean-mode ratio (guarded, no
   faults) is recorded alongside: the price of the rails when nothing is.
2. **chaos** -- the zero-request-loss gate: all three apps served by one
   ``AsyncPlanServer`` (background scheduler thread) under a seeded 5%
   kernel-failure rate, submissions through the jittered-backoff retry
   helper.  Every request must complete within 1e-4 of the reference
   plan, the scheduler thread must survive, and the injected faults must
   actually have fired (a chaos run with no chaos gates nothing).
3. **chaos_total** -- the same traffic under a 100% failure rate: every
   step demotes and every result must be bit-exact vs reference.
4. **recovery** -- breaker lifecycle on an injected clock: sustained
   failures trip every breaker open; with the faults gone and the cooldown
   elapsed, one probe pass must close them all again.

All fault decisions come from one seeded RNG (``--seed``, default from
``REPRO_FAULT_SEED``), so a run is reproducible fault-for-fault.
``--smoke`` shrinks shapes and traffic for CI (wired into
``make chaos-smoke`` / ``make bench-smoke``).
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
from repro.robustness import FaultPlan, FaultRule, GuardConfig
from repro.serving import AsyncPlanServer, submit_with_retry
from repro.utils.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _median_ms(fn, reps: int) -> float:
    fn()  # warm: compile/caches outside the timed window
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


def _build(smoke: bool, guard: GuardConfig | None = None):
    """(guarded plan, reference plan, params, frame shape) per app."""
    base, size = (8, 12) if smoke else (16, 16)
    built = {}
    for app in APPS:
        g = APPS[app](jax.random.PRNGKey(0), base=base)
        built[app] = (
            compile_plan(g, backend="guarded", guard=guard),
            compile_plan(g, backend="reference"),
            g.params,
            (1 if app == "coloring" else 3, size, size),
        )
    return built


def bench_robustness(
    smoke: bool = False, seed: int = 7, out_path: str | None = None
) -> dict:
    record: dict = {
        "mode": "interpret",  # guarded plans are eager; wall-clock is Python
        "smoke": smoke,
        "seed": seed,
        "degraded": [],
        "chaos": {},
        "chaos_total": {},
        "recovery": {},
    }
    reps = 3 if smoke else 5
    frames_per_app = 4 if smoke else 8
    batch_size = 2 if smoke else 4
    rng = np.random.default_rng(0)

    # 1. degraded-mode overhead: guarded-under-total-failure vs reference.
    print("robustness_degraded,app,ref_ms,degraded_ms,overhead,bitexact")
    built = _build(smoke)
    for app, (plan, ref, params, shape) in built.items():
        x = jnp.asarray(rng.standard_normal((batch_size, *shape)), jnp.float32)
        y_ref = np.asarray(ref(params, x))
        ref_ms = _median_ms(lambda: ref(params, x), reps)
        clean_ms = _median_ms(lambda: plan(params, x), reps)
        with FaultPlan([FaultRule("*", "raise", rate=1.0)], seed=seed):
            y_deg = np.asarray(plan(params, x))
            deg_ms = _median_ms(lambda: plan(params, x), reps)
        bitexact = bool(np.array_equal(y_deg, y_ref))
        assert bitexact, app  # the fallback IS the reference: exact or bust
        row = {
            "app": app,
            "ref_ms": ref_ms,
            "clean_ms": clean_ms,
            "degraded_ms": deg_ms,
            "overhead": deg_ms / ref_ms,
            "clean_overhead": clean_ms / ref_ms,
            "max_err": 0.0,
            "bitexact": bitexact,
            "fallbacks": plan.guard_stats()["counters"]["fallbacks"],
        }
        record["degraded"].append(row)
        print(
            f"robustness_degraded,{app},{ref_ms:.2f},{deg_ms:.2f},"
            f"{row['overhead']:.2f}x,{bitexact}"
        )

    # 2 + 3. chaos scenarios through the async server (fresh plans so the
    # breaker/counter state starts clean; one server thread hosts all apps).
    def chaos_scenario(rate: float) -> dict:
        built = _build(smoke)
        server = AsyncPlanServer(flush_after=0.005, tick_interval=0.001)
        for app, (plan, _ref, params, shape) in built.items():
            server.add_plan(
                app, plan, params, batch_size,
                input_spec=[(shape, jnp.float32)],
            )
        frames = {
            app: [
                jnp.asarray(rng.standard_normal(built[app][3]), jnp.float32)
                for _ in range(frames_per_app)
            ]
            for app in built
        }
        with server:
            server.start()
            for app in built:  # warm each path outside the chaos window
                server.submit(app, frames[app][0]).result(120)
            t0 = time.perf_counter()
            with FaultPlan([FaultRule("*", "raise", rate=rate)], seed=seed) as fp:
                handles = [
                    (app, f, submit_with_retry(server, app, f, backoff=0.001))
                    for app in built
                    for f in frames[app]
                ]
                results = [(app, f, h, h.result(600)) for app, f, h in handles]
                injected = fp.injection_count()
            wall = time.perf_counter() - t0
            lost = sum(1 for _, _, h, _ in results if h.exception() is not None)
            max_err, exact = 0.0, True
            for app, f, _h, y in results:
                _plan, ref, params, _shape = built[app]
                y_ref = np.asarray(ref(params, f[None]))[0]
                max_err = max(max_err, float(np.max(np.abs(np.asarray(y) - y_ref))))
                exact = exact and bool(np.array_equal(np.asarray(y), y_ref))
            stats = server.stats
            health = server.health()
            out = {
                "rate": rate,
                "requests": len(handles),
                "lost_requests": lost,
                "injected_faults": injected,
                "fallbacks": sum(
                    p.get("guard", {}).get("counters", {}).get("fallbacks", 0)
                    for p in health["plans"].values()
                ),
                "breaker_trips": sum(
                    b["trips"]
                    for p in health["plans"].values()
                    for b in p.get("guard", {}).get("breakers", {}).values()
                ),
                "max_err": max_err,
                "bitexact": exact,
                "scheduler_survived": bool(
                    server.running and health["tick_errors"] == 0
                ),
                "watchdog_timeouts": stats["watchdog_timeouts"],
                "wall_s": wall,
            }
        # the chaos gate proper: zero loss, surviving scheduler, real chaos
        assert out["lost_requests"] == 0, out
        assert out["scheduler_survived"], out
        assert out["injected_faults"] >= 1, "chaos run injected nothing"
        assert out["max_err"] <= 1e-4, out
        return out

    record["chaos"] = chaos_scenario(0.05)
    c = record["chaos"]
    print(
        f"robustness_chaos,rate=0.05,requests={c['requests']},"
        f"lost={c['lost_requests']},injected={c['injected_faults']},"
        f"fallbacks={c['fallbacks']},max_err={c['max_err']:.2e},"
        f"survived={c['scheduler_survived']}"
    )
    record["chaos_total"] = chaos_scenario(1.0)
    ct = record["chaos_total"]
    assert ct["bitexact"], ct  # total demotion must reproduce the oracle
    print(
        f"robustness_chaos_total,rate=1.0,requests={ct['requests']},"
        f"lost={ct['lost_requests']},bitexact={ct['bitexact']},"
        f"trips={ct['breaker_trips']}"
    )

    # 4. breaker recovery on an injected clock: trip everything, lift the
    # faults, let the cooldown elapse, and one probe pass must close it all.
    clk = _Clock()
    cfg = GuardConfig(breaker_threshold=2, breaker_cooldown=5.0, clock=clk)
    built = _build(True, guard=cfg)  # tiny shapes: lifecycle, not perf
    app, (plan, ref, params, shape) = next(iter(built.items()))
    x = jnp.asarray(rng.standard_normal((2, *shape)), jnp.float32)
    with FaultPlan([FaultRule("*", "raise", rate=1.0)], seed=seed):
        for _ in range(3):  # enough passes to trip every per-op breaker
            plan(params, x)
    states = {b["state"] for b in plan.guard_stats()["breakers"].values()}
    trips = sum(b["trips"] for b in plan.guard_stats()["breakers"].values())
    assert "open" in states and trips >= 1, (states, trips)
    clk.advance(5.0)  # cooldown elapses; faults are gone
    y = plan(params, x)
    after = {b["state"] for b in plan.guard_stats()["breakers"].values()}
    recovered = after == {"closed"}
    assert recovered, after
    assert np.allclose(np.asarray(y), np.asarray(ref(params, x)), atol=1e-4)
    record["recovery"] = {
        "app": app,
        "breaker_trips": trips,
        "states_while_tripped": sorted(states),
        "states_after_cooldown": sorted(after),
        "recovered": recovered,
    }
    print(f"robustness_recovery,{app},trips={trips},recovered={recovered}")

    # smoke numbers are CI plumbing, not perf data: never clobber the
    # cross-PR trajectory artifact with them
    default_name = (
        "BENCH_robustness_smoke.json" if smoke else "BENCH_robustness.json"
    )
    out_path = out_path or os.path.join(RESULTS_DIR, default_name)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"robustness,saved,{os.path.abspath(out_path)}")
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny shapes (CI, no TPU)")
    ap.add_argument(
        "--seed", type=int,
        default=int(os.environ.get("REPRO_FAULT_SEED", "7")),
        help="fault-injection seed (env REPRO_FAULT_SEED)",
    )
    args = ap.parse_args()
    bench_robustness(smoke=args.smoke, seed=args.seed)
