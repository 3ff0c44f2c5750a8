"""Chaos suite: guarded execution, fault injection, breakers, serving
hardening.

The headline test is the chaos gate from the PR's acceptance criteria:
with a seeded 5% injected kernel-failure rate across all three demo apps
served through ``AsyncPlanServer``, 100% of submitted requests complete
(reference fallback), the scheduler thread survives, and under a *total*
failure rate the results are bit-identical to the pure reference plan.
Everything here is deterministic -- fault decisions come from seeded RNGs,
breaker cooldowns from injected clocks, retry backoff from injected sleep.
"""

import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import restore_global_state, snapshot_global_state

from repro.core.graph import (
    GraphBuilder,
    compile_plan,
    guard_fallback_counts,
)
from repro.core.graph.executor import EXEC_BACKENDS
from repro.kernels import ops as kops
from repro.models.cnn import APPS
from repro.robustness import (
    BreakerOpen,
    CircuitBreaker,
    FaultPlan,
    FaultRule,
    GuardConfig,
    InjectedFault,
    active_fault_plan,
    uninstall_all,
)
from repro.serving import (
    AsyncPlanServer,
    QueueFullError,
    SwapError,
    WatchdogTimeout,
    submit_with_retry,
)
from repro.utils.retry import retry_call

KEY = jax.random.PRNGKey(0)


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _tiny(backend="guarded", guard=None, n=8):
    """One-linear-layer graph: the smallest demotable plan."""
    b = GraphBuilder(["x"])
    w = jax.random.normal(KEY, (n, n), jnp.float32)
    y = b.add("linear", "x", params={"w": w})
    g = b.build(y)
    return g, compile_plan(g, backend=backend, guard=guard)


# --------------------------------------------------------------------------- #
# circuit breaker state machine                                                #
# --------------------------------------------------------------------------- #


def test_breaker_trips_after_threshold_within_window():
    clk = Clock()
    br = CircuitBreaker(threshold=3, window=10.0, cooldown=5.0, clock=clk)
    assert br.state == "closed" and br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == "closed" and br.allow()  # under threshold
    br.record_failure()
    assert br.state == "open" and br.trips == 1
    assert not br.allow()  # cooldown not elapsed
    with pytest.raises(BreakerOpen):
        br.raise_if_open()


def test_breaker_window_prunes_stale_failures():
    clk = Clock()
    br = CircuitBreaker(threshold=3, window=10.0, clock=clk)
    br.record_failure()
    br.record_failure()
    clk.advance(11.0)  # both failures age out of the window
    br.record_failure()
    assert br.state == "closed"


def test_breaker_half_open_probe_recovers_or_reopens():
    clk = Clock()
    br = CircuitBreaker(threshold=1, cooldown=5.0, clock=clk)
    br.record_failure()
    assert br.state == "open" and not br.allow()
    clk.advance(5.0)
    assert br.allow() and br.state == "half_open"  # one probe allowed
    br.record_failure()  # probe failed: reopen, cooldown restarts
    assert br.state == "open" and br.trips == 2 and not br.allow()
    clk.advance(5.0)
    assert br.allow() and br.state == "half_open"
    br.record_success()  # probe succeeded: full recovery
    assert br.state == "closed" and br.allow()
    assert br.snapshot() == {"state": "closed", "trips": 2, "recent_failures": 0}


def test_breaker_rejects_bad_threshold():
    with pytest.raises(ValueError, match="threshold"):
        CircuitBreaker(threshold=0)


# --------------------------------------------------------------------------- #
# fault plans                                                                  #
# --------------------------------------------------------------------------- #


def test_fault_rule_validates_kind_and_rate():
    with pytest.raises(ValueError, match="kind"):
        FaultRule("matmul", "explode")
    with pytest.raises(ValueError, match="rate"):
        FaultRule("matmul", "raise", rate=1.5)


def test_install_patches_and_uninstall_restores_entry_points():
    orig = kops.matmul
    x = jnp.ones((4, 4), jnp.float32)
    with FaultPlan([FaultRule("matmul", "raise", rate=1.0)], seed=0) as fp:
        assert kops.matmul is not orig
        with pytest.raises(InjectedFault):
            kops.matmul(x, x, interpret=True)
        assert fp.injection_count("matmul") == 1
        assert active_fault_plan() is fp
    assert kops.matmul is orig
    assert active_fault_plan() is None
    # and the restored entry point works
    y = kops.matmul(x, x, interpret=True)
    assert np.allclose(np.asarray(y), 4.0)


def test_seeded_injection_sequence_is_deterministic():
    def pattern(seed):
        fp = FaultPlan([FaultRule("matmul", "raise", rate=0.3)], seed=seed)
        fn = fp.wrap("matmul", lambda: "ok")
        seq = []
        for _ in range(200):
            try:
                fn()
                seq.append(0)
            except InjectedFault:
                seq.append(1)
        return seq

    a, b, c = pattern(7), pattern(7), pattern(8)
    assert a == b  # same seed, same call order -> identical faults
    assert a != c
    assert 30 <= sum(a) <= 90  # ~0.3 rate over 200 calls
    assert len(a) == 200


def test_nan_and_inf_poisoning():
    x = jnp.ones((4, 4), jnp.float32)
    with FaultPlan([FaultRule("matmul", "nan", rate=1.0)], seed=0):
        y = kops.matmul(x, x, interpret=True)
        assert bool(jnp.all(jnp.isnan(y)))
    with FaultPlan([FaultRule("matmul", "inf", rate=1.0)], seed=0):
        y = kops.matmul(x, x, interpret=True)
        assert bool(jnp.all(jnp.isinf(y)))


def test_latency_injection_uses_injectable_sleep():
    slept = []
    fp = FaultPlan(
        [FaultRule("matmul", "latency", rate=1.0, delay=0.25)],
        seed=0, sleep=slept.append,
    )
    x = jnp.ones((4, 4), jnp.float32)
    with fp:
        y = kops.matmul(x, x, interpret=True)
    assert slept == [0.25]
    assert np.allclose(np.asarray(y), 4.0)  # latency never corrupts output


def test_cache_corrupt_rule_zeroes_existing_entries():
    cache = kops.tuning_cache()
    k = kops.TuningCache.key("matmul", 64, 64, 64, jnp.float32, "dense", True)
    cache.entries[k] = kops.TuneEntry((64, 128, 128), "swept", 0.3)
    with FaultPlan([FaultRule("*", "cache_corrupt", rate=1.0)], seed=0) as fp:
        assert k in fp.corrupted_keys
        assert cache.entries[k].blocks == (0, 0, 0)
        assert fp.injection_count("tuning_cache") >= 1
    # conftest's autouse fixture restores the cache; nothing to clean here


def test_double_install_raises_and_uninstall_all_sweeps():
    fp1 = FaultPlan([FaultRule("matmul", "raise")]).install()
    fp2 = FaultPlan([FaultRule("conv2d", "raise")]).install()
    with pytest.raises(RuntimeError, match="already installed"):
        fp1.install()
    assert active_fault_plan() is fp2
    assert uninstall_all() == 2
    assert active_fault_plan() is None


# --------------------------------------------------------------------------- #
# guarded executor                                                             #
# --------------------------------------------------------------------------- #


def test_guarded_backend_is_listed_and_validated():
    assert "guarded" in EXEC_BACKENDS
    g, _ = _tiny(backend="reference")
    with pytest.raises(ValueError, match="guarded"):
        compile_plan(g, backend="bogus")


def test_guard_config_requires_guarded_backend():
    b = GraphBuilder(["x"])
    y = b.add("linear", "x", params={"w": jnp.eye(4)})
    g = b.build(y)
    with pytest.raises(ValueError, match="guard"):
        compile_plan(g, backend="reference", guard=GuardConfig())


def test_guarded_matches_reference_without_faults():
    g, plan = _tiny()
    ref = compile_plan(g, backend="reference")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8))
    err = float(jnp.max(jnp.abs(plan(g.params, x) - ref(g.params, x))))
    assert err <= 1e-5
    stats = plan.guard_stats()
    assert stats["counters"]["primary_ok"] == 1
    assert stats["counters"]["fallbacks"] == 0


def test_total_faults_demote_bitexact_with_exact_counters():
    g, plan = _tiny()
    ref = compile_plan(g, backend="reference")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8))
    y_ref = ref(g.params, x)
    base = guard_fallback_counts().get("linear/f32/exception", 0)
    with FaultPlan([FaultRule("linear", "raise", rate=1.0)], seed=0):
        y = plan(g.params, x)
    assert np.array_equal(np.asarray(y), np.asarray(y_ref))  # bit-correct
    c = plan.guard_stats()["counters"]
    assert c["fallbacks"] == 1 and c["primary_ok"] == 0
    assert c["by_key"] == {"linear/f32/exception": 1}
    # process-wide accounting extends (not duplicates) the ops-style counters
    assert guard_fallback_counts()["linear/f32/exception"] == base + 1


def test_numeric_guard_demotes_poisoned_output():
    g, plan = _tiny()
    ref = compile_plan(g, backend="reference")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8))
    with FaultPlan([FaultRule("linear", "nan", rate=1.0)], seed=0):
        y = plan(g.params, x)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert np.array_equal(np.asarray(y), np.asarray(ref(g.params, x)))
    c = plan.guard_stats()["counters"]
    assert c["numeric_guard_trips"] == 1
    assert c["by_key"] == {"linear/f32/numeric": 1}


def test_numeric_guard_can_be_disabled():
    g, plan = _tiny(guard=GuardConfig(numeric_guards=False))
    x = jnp.ones((2, 8), jnp.float32)
    with FaultPlan([FaultRule("linear", "nan", rate=1.0)], seed=0):
        y = plan(g.params, x)
    assert bool(jnp.all(jnp.isnan(y)))  # poison flows through, no demotion
    assert plan.guard_stats()["counters"]["fallbacks"] == 0


def test_breaker_pins_to_reference_then_recovers_after_cooldown():
    clk = Clock()
    cfg = GuardConfig(breaker_threshold=2, breaker_cooldown=5.0, clock=clk)
    g, plan = _tiny(guard=cfg)
    ref = compile_plan(g, backend="reference")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8))
    y_ref = np.asarray(ref(g.params, x))
    with FaultPlan([FaultRule("linear", "raise", rate=1.0)], seed=0):
        plan(g.params, x)  # failure 1
        plan(g.params, x)  # failure 2 -> breaker opens
        assert plan.guard_stats()["breakers"]["linear/f32"]["state"] == "open"
        plan(g.params, x)  # short-circuits: no primary attempt, no new trip
    c = plan.guard_stats()["counters"]
    assert c["breaker_short_circuits"] == 1
    assert c["by_key"]["linear/f32/breaker_open"] == 1
    # faults gone, but the breaker is still open: stays pinned to reference
    assert np.array_equal(np.asarray(plan(g.params, x)), y_ref)
    assert plan.guard_stats()["counters"]["breaker_short_circuits"] == 2
    # cooldown elapses -> half-open probe runs the (healthy) kernel -> closed
    clk.advance(5.0)
    plan(g.params, x)
    br = plan.guard_stats()["breakers"]["linear/f32"]
    assert br == {"state": "closed", "trips": 1, "recent_failures": 0}
    assert plan.guard_stats()["counters"]["primary_ok"] >= 1


def test_qlinear_scheme_keys_breakers_separately():
    """A quantized node's breaker key carries its scheme, so a broken INT8
    kernel never opens the f32 family's breaker."""
    b = GraphBuilder(["x"])
    wq = jnp.ones((8, 8), jnp.int8)
    y = b.add(
        "qlinear", "x",
        params={"values": wq, "w_scale": jnp.ones((8,), jnp.float32)},
        format="dense", scheme="w8",
    )
    g = b.build(y)
    plan = compile_plan(g, backend="guarded")
    x = jnp.ones((2, 8), jnp.float32)
    with FaultPlan([FaultRule("qlinear", "raise", rate=1.0)], seed=0):
        plan(g.params, x)
    assert plan.guard_stats()["counters"]["by_key"] == {
        "qlinear/w8/exception": 1
    }


def test_corrupted_tuning_cache_recovers_through_guarded_plan():
    """cache_corrupt chaos: degenerate tuned blocks crash the kernel path;
    the guarded plan absorbs it per-step and still returns correct output."""
    g, plan = _tiny(n=16)
    ref = compile_plan(g, backend="reference")
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    # seed a (bogus) swept winner for this shape, then corrupt every entry
    cache = kops.tuning_cache()
    k = kops.TuningCache.key("matmul", 4, 16, 16, jnp.float32, "dense", True)
    cache.entries[k] = kops.TuneEntry((8, 128, 128), "swept", 0.1)
    with FaultPlan([FaultRule("*", "cache_corrupt", rate=1.0)], seed=0):
        y = plan(g.params, x)
    assert np.array_equal(np.asarray(y), np.asarray(ref(g.params, x)))
    assert plan.guard_stats()["counters"]["fallbacks"] >= 1


def test_batched_guarded_plan_is_eager_and_rejects_vmap():
    g, plan = _tiny()
    with pytest.raises(ValueError, match="eager"):
        plan.batched(2, via_vmap=True)
    bp = plan.batched(2)
    x = jnp.ones((3, 8), jnp.float32)  # padded tail chunk
    with FaultPlan([FaultRule("linear", "raise", rate=1.0)], seed=0):
        y = bp(g.params, x)
    assert y.shape == (3, 8)
    assert plan.guard_stats()["counters"]["fallbacks"] == 2  # two chunks


def test_guard_counters_restore_via_conftest_snapshot():
    """The state-isolation machinery covers guard counters and installed
    fault plans exactly like the conv/tuning state."""
    baseline = snapshot_global_state()
    g, plan = _tiny()
    snap = snapshot_global_state()
    FaultPlan([FaultRule("linear", "raise", rate=1.0)], seed=0).install()
    plan(g.params, jnp.ones((2, 8), jnp.float32))
    assert guard_fallback_counts()["linear/f32/exception"] >= 1
    assert active_fault_plan() is not None
    restore_global_state(snap)
    assert snapshot_global_state() == baseline
    assert active_fault_plan() is None  # leaked install force-removed


# --------------------------------------------------------------------------- #
# retry helper                                                                 #
# --------------------------------------------------------------------------- #


def test_retry_call_backoff_schedule_with_jitter():
    delays, attempts = [], []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 3:
            raise OSError("transient")
        return "ok"

    out = retry_call(
        flaky, retries=5, backoff=1.0, backoff_factor=2.0, jitter=0.5,
        sleep=delays.append, rng=random.Random(0),
        on_retry=lambda i, e: attempts.append(i),
    )
    assert out == "ok" and calls["n"] == 4
    assert attempts == [0, 1, 2]
    assert len(delays) == 3
    # full-jitter bounds: delay_i in [base_i, base_i * 1.5)
    for d, base in zip(delays, [1.0, 2.0, 4.0]):
        assert base <= d < base * 1.5


def test_retry_call_exhaustion_reraises_and_validates():
    with pytest.raises(OSError):
        retry_call(
            lambda: (_ for _ in ()).throw(OSError("nope")),
            retries=2, sleep=lambda _: None,
        )
    with pytest.raises(ValueError, match="retries"):
        retry_call(lambda: 1, retries=-1)
    with pytest.raises(ValueError, match="jitter"):
        retry_call(lambda: 1, jitter=-0.1)


def test_training_retry_backcompat_delegates():
    from repro.training.fault_tolerance import retry

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("once")
        return 42

    assert retry(flaky, retries=1, backoff=0.0) == 42


# --------------------------------------------------------------------------- #
# serving hardening: watchdog, health, submit retry                            #
# --------------------------------------------------------------------------- #


def _tiny_server(**kw):
    g, plan = _tiny()
    server = AsyncPlanServer(**kw)
    server.add_plan("tiny", plan, g.params, batch_size=2)
    return g, plan, server


def test_watchdog_fails_hung_batch_scheduler_survives():
    g, plan, server = _tiny_server(watchdog=0.1, clock=time.monotonic)
    x = jnp.ones((8,), jnp.float32)
    h0 = server.submit("tiny", x)  # warm (compile) outside the fault window
    server.step(force=True)
    assert h0.result(5).shape == (8,)
    release = threading.Event()
    fp = FaultPlan(
        [FaultRule("linear", "latency", rate=1.0, delay=0.0)],
        seed=0, sleep=lambda _: release.wait(10),
    ).install()
    try:
        h = server.submit("tiny", x)
        server.step(force=True)  # worker hangs; watchdog deadline fires
        assert h.done()
        assert isinstance(h.exception(), WatchdogTimeout)
        assert server.stats["per_plan"]["tiny"]["watchdog_timeouts"] == 1
    finally:
        release.set()  # unblock the abandoned worker thread
        fp.uninstall()
    # the abandoned worker finishing late must not overwrite the verdict
    time.sleep(0.05)
    assert isinstance(h.exception(), WatchdogTimeout)
    # and the scheduler keeps serving
    h2 = server.submit("tiny", x)
    server.step(force=True)
    assert h2.exception() is None and h2.result(1).shape == (8,)
    server.close()


def test_scheduler_thread_survives_tick_errors():
    _, _, server = _tiny_server(
        clock=time.monotonic, tick_interval=0.001, flush_after=0.005
    )
    boom = {"n": 0}
    real_step = server.step

    def bad_step(**kw):
        if boom["n"] < 3:
            boom["n"] += 1
            raise RuntimeError("injected tick failure")
        return real_step(**kw)

    server.step = bad_step
    server.start()
    deadline = time.monotonic() + 5
    while boom["n"] < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert boom["n"] == 3
    assert server.running  # thread survived every bad tick
    assert server.health()["tick_errors"] == 3
    del server.step  # restore the real method for the drain in close()
    h = server.submit("tiny", jnp.ones((8,), jnp.float32))
    assert h.result(5).shape == (8,)
    server.close()
    assert not server.running


def test_health_snapshot_shape():
    g, plan, server = _tiny_server(clock=lambda: 0.0)
    with FaultPlan([FaultRule("linear", "raise", rate=1.0)], seed=0):
        h = server.submit("tiny", jnp.ones((8,), jnp.float32))
        server.step(force=True)
    assert h.exception() is None  # guarded plan absorbed the fault
    health = server.health()
    assert health["running"] is False and health["closed"] is False
    assert health["pending"] == 0 and health["tick_errors"] == 0
    tiny = health["plans"]["tiny"]
    assert tiny["queue_depth"] == 0
    assert tiny["stats"]["completed"] == 1
    guard = tiny["guard"]
    assert guard["counters"]["fallbacks"] >= 1
    assert "linear/f32" in guard["breakers"]
    server.close()


def test_submit_with_retry_rides_out_backpressure():
    _, _, server = _tiny_server(clock=lambda: 0.0, max_queue=1)
    h1 = server.submit("tiny", jnp.ones((8,), jnp.float32))
    # queue is now full; the retry helper drains it between attempts
    h2 = submit_with_retry(
        server, "tiny", jnp.ones((8,), jnp.float32),
        retries=3, backoff=0.001,
        sleep=lambda _: server.step(force=True),
    )
    server.step(force=True)
    assert h1.result(1).shape == (8,) and h2.result(1).shape == (8,)
    # a queue that stays full exhausts the retries and still raises
    server.submit("tiny", jnp.ones((8,), jnp.float32))
    with pytest.raises(QueueFullError):
        submit_with_retry(
            server, "tiny", jnp.ones((8,), jnp.float32),
            retries=2, backoff=0.001, sleep=lambda _: None,
        )
    server.close()


# --------------------------------------------------------------------------- #
# the chaos gate (acceptance criteria)                                         #
# --------------------------------------------------------------------------- #


@pytest.mark.slow
def test_chaos_gate_all_apps_zero_loss_and_bitexact_fallback():
    """Acceptance gate: all three demo apps served by one AsyncPlanServer
    under a seeded 5% kernel-failure rate -- every request completes, close
    to reference; under a 100% rate every step demotes and the results are
    bit-identical to the pure reference plans; the scheduler thread never
    dies; breakers trip under sustained failure and recover after cooldown."""
    clk = Clock()
    size, frames_per_app = 12, 4
    server = AsyncPlanServer(flush_after=0.005, clock=time.monotonic)
    plans, refs, shapes = {}, {}, {}
    for app in APPS:
        g = APPS[app](jax.random.PRNGKey(0), base=8)
        cfg = GuardConfig(breaker_threshold=3, breaker_cooldown=5.0, clock=clk)
        plans[app] = (compile_plan(g, backend="guarded", guard=cfg), g.params)
        refs[app] = compile_plan(g, backend="reference")
        c_in = 1 if app == "coloring" else 3
        shapes[app] = (c_in, size, size)
        server.add_plan(
            app, plans[app][0], g.params, batch_size=2,
            input_spec=[(shapes[app], jnp.float32)],
        )
    rng = np.random.default_rng(0)
    frames = {
        app: [
            jnp.asarray(rng.standard_normal(shapes[app]), jnp.float32)
            for _ in range(frames_per_app)
        ]
        for app in APPS
    }
    with server:
        server.start()
        for app in APPS:  # warm each app's path outside the chaos window
            server.submit(app, frames[app][0]).result(60)

        def serve_all():
            handles = [
                (app, f, submit_with_retry(server, app, f, backoff=0.001))
                for app in APPS
                for f in frames[app]
            ]
            results = [(app, f, h.result(120)) for app, f, h in handles]
            assert all(h.exception() is None for _, _, h in handles)
            return results

        # scenario 1: 5% failure rate -- zero loss, close to reference
        with FaultPlan([FaultRule("*", "raise", rate=0.05)], seed=7) as fp:
            results = serve_all()
        assert len(results) == 3 * frames_per_app  # 100% completion
        for app, f, y in results:
            y_ref = refs[app](plans[app][1], f[None])
            err = float(jnp.max(jnp.abs(jnp.asarray(y) - jnp.asarray(y_ref)[0])))
            assert err <= 1e-4, (app, err)
        assert fp.injection_count() >= 1  # chaos actually happened

        # scenario 2: total failure -- every step demotes, bit-exact results.
        # A guarded chunk runs eagerly at the compiled batch (2; a short
        # chunk is zero-padded), so the reference runs eagerly at that batch
        # too: XLA's CPU code for a batch of 1 differs in the last bits
        with FaultPlan([FaultRule("*", "raise", rate=1.0)], seed=7):
            results = serve_all()
        for app, f, y in results:
            y_ref = refs[app](plans[app][1], jnp.stack([f, jnp.zeros_like(f)]))
            assert np.array_equal(np.asarray(y), np.asarray(y_ref)[0]), app

        # the sustained failures tripped breakers on every app...
        tripped = {
            app
            for app in APPS
            for b in plans[app][0].guard_stats()["breakers"].values()
            if b["trips"] >= 1
        }
        assert tripped == set(APPS)
        # ...and with the faults gone + cooldown elapsed they close again
        clk.advance(5.0)
        for app in APPS:
            server.submit(app, frames[app][0]).result(60)
        for app in APPS:
            states = {
                b["state"]
                for b in plans[app][0].guard_stats()["breakers"].values()
            }
            assert states == {"closed"}, (app, states)
        assert server.running  # the scheduler thread survived all of it
        assert server.health()["tick_errors"] == 0
        total = server.stats
        assert total["completed"] == total["submitted"]  # zero request loss
        assert total["bad_frames"] == 0 and total["watchdog_timeouts"] == 0


@pytest.mark.slow
def test_chaos_gate_hot_swap_all_apps_zero_loss():
    """Acceptance gate (PR 9): swap all three demo-app plans mid-traffic
    under the seeded 5% chaos rate -- 100% of admitted requests complete at
    parity with the reference plan *of the version that served them*, every
    old version drains and retires, and the rollback path is exercised (a
    poisoned incoming version must never install)."""

    def scale(params, factor):
        return jax.tree_util.tree_map(
            lambda a: a * factor
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
            params,
        )

    size = 12
    server = AsyncPlanServer(flush_after=0.005, clock=time.monotonic)
    plans, refs, shapes, frames, vparams = {}, {}, {}, {}, {}
    rng = np.random.default_rng(0)
    for app in APPS:
        g = APPS[app](jax.random.PRNGKey(0), base=8)
        cfg = GuardConfig(breaker_threshold=100)
        plans[app] = compile_plan(g, backend="guarded", guard=cfg)
        refs[app] = compile_plan(g, backend="reference")
        c_in = 1 if app == "coloring" else 3
        shapes[app] = (c_in, size, size)
        vparams[app] = {0: g.params, 1: scale(g.params, 0.5)}
        frames[app] = [
            jnp.asarray(rng.standard_normal(shapes[app]), jnp.float32)
            for _ in range(6)
        ]
        server.add_plan(
            app, plans[app], g.params, batch_size=2,
            input_spec=[(shapes[app], jnp.float32)],
        )
    with server:
        server.start()
        for app in APPS:  # warm each app's path outside the chaos window
            server.submit(app, frames[app][0]).result(60)

        def submit_all(lo, hi):
            return [
                (app, f, submit_with_retry(server, app, f, backoff=0.001))
                for app in APPS
                for f in frames[app][lo:hi]
            ]

        with FaultPlan([FaultRule("*", "raise", rate=0.05)], seed=7) as fp:
            handles = submit_all(0, 3)  # admitted on v0
            for app in APPS:  # swap every plan while that traffic is live
                assert server.swap_plan(
                    app, plans[app], vparams[app][1],
                    probe_frames=[frames[app][0]],
                ) == 1
            # rollback path: a poisoned version must fail its probe and
            # leave the freshly installed v1 serving
            with pytest.raises(SwapError, match="non-finite"):
                server.swap_plan(
                    "coloring", plans["coloring"],
                    scale(vparams["coloring"][0], np.nan),
                    probe_frames=[frames["coloring"][0]],
                )
            handles += submit_all(3, 6)  # admitted on v1
            versions = {id(h): h._runner.version for _, _, h in handles}
            results = [(app, f, h, h.result(120)) for app, f, h in handles]
        assert fp.injection_count() >= 1  # chaos actually happened
        assert len(results) == 3 * 6  # 100% completion: zero request loss
        for app, f, h, y in results:
            want = refs[app](vparams[app][versions[id(h)]], f[None])
            err = float(jnp.max(jnp.abs(jnp.asarray(y) - jnp.asarray(want)[0])))
            assert err <= 1e-4, (app, versions[id(h)], err)
        # both versions actually served traffic on every app
        assert all(
            {versions[id(h)] for a, _, h in handles if a == app} == {0, 1}
            for app in APPS
        )
        health = server.health()
        s = server.stats
        for app in APPS:
            assert health["plans"][app]["version"] == 1
            assert "draining" not in health["plans"][app]  # v0 retired
        assert s["swaps"] == 3 and s["versions_retired"] == 3
        assert s["swap_rollbacks"] == 1
        assert s["completed"] == s["submitted"]
        assert server.health()["tick_errors"] == 0


@pytest.mark.slow
def test_chaos_gate_decode_zero_sequence_loss():
    """Acceptance gate (PR 10): autoregressive decode through guarded
    prefill/decode plans under the seeded 5% kernel-failure rate -- every
    sequence completes (per-step demotion absorbs faults before they can
    fail a batch), the generated tokens match the naive jnp greedy loop,
    and no KV-cache page leaks; under a 100% rate every step demotes and
    the tokens are still golden (reference fallback is bit-correct)."""
    from repro.configs.registry import smoke_config
    from repro.core.graph.passes import optimize
    from repro.models.transformer import forward, init_lm
    from repro.models.transformer_graph import (
        build_decoder_graph,
        decoder_cache_spec,
    )
    from repro.serving import PagedKVCache

    cfg = smoke_config("qwen2.5-3b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    guard = GuardConfig(breaker_threshold=100)
    plans, graphs = {}, {}
    for phase in ("prefill", "decode"):
        graphs[phase] = optimize(build_decoder_graph(params, cfg, phase=phase))
        plans[phase] = compile_plan(
            graphs[phase], backend="guarded", guard=guard, interpret=True
        )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (4, 6, 3, 8)]

    def naive(prompt, steps):
        seq = [int(t) for t in prompt]
        for _ in range(steps):
            logits, _ = forward(params, cfg, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(logits[0, -1])))
        return seq[len(prompt):]

    want = [naive(p, 3) for p in prompts]

    def serve_all():
        cache = PagedKVCache(num_pages=32, page_size=4,
                             **decoder_cache_spec(cfg))
        server = AsyncPlanServer()
        server.add_llm("lm", prefill=plans["prefill"],
                       decode=plans["decode"], cache=cache, max_batch=2)
        handles = [server.submit_llm("lm", p, max_new_tokens=3)
                   for p in prompts]
        while any(not h.done() for h in handles):
            server.step()
        st = server.stats["per_llm"]["lm"]
        server.close()
        cache.check_invariants()
        assert cache.used_pages == 0  # zero page leak
        return handles, st

    # scenario 1: 5% failure rate -- zero sequence loss, golden tokens
    with FaultPlan([FaultRule("*", "raise", rate=0.05)], seed=7) as fp:
        handles, st = serve_all()
    assert fp.injection_count() >= 1  # chaos actually happened
    assert st["failed"] == 0 and st["completed"] == len(prompts)
    for h, w in zip(handles, want):
        assert h.exception() is None
        assert [int(t) for t in h.result(0)] == w

    # scenario 2: total failure -- every step demotes, tokens still golden
    base = sum(
        plans[p].guard_stats()["counters"]["fallbacks"]
        for p in ("prefill", "decode")
    )
    with FaultPlan([FaultRule("*", "raise", rate=1.0)], seed=7):
        handles, st = serve_all()
    assert st["failed"] == 0
    for h, w in zip(handles, want):
        assert [int(t) for t in h.result(0)] == w
    demoted = sum(
        plans[p].guard_stats()["counters"]["fallbacks"]
        for p in ("prefill", "decode")
    )
    assert demoted > base  # the fallback path genuinely carried the traffic


def test_demotions_surface_in_registry_and_trace():
    """Chaos observability contract (make chaos-smoke): a guarded run under
    fault injection reports every demotion BOTH ways -- as registry counters
    (guard_demotions_total, the guard_fallback_counts view) and as trace
    annotations (a ``demoted`` arg on the step span plus a cat="guard"
    instant), and the two accounts agree event-for-event."""
    from repro.obs import metrics, trace

    g, plan = _tiny()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8))
    with FaultPlan([FaultRule("linear", "raise", rate=1.0)], seed=0):
        with trace.tracing() as buf:
            plan(g.params, x)
    # registry side
    assert guard_fallback_counts()["linear/f32/exception"] == 1
    series = metrics.registry().counter(
        "guard_demotions_total", op="linear", scheme="f32", reason="exception"
    )
    assert series.value == 1
    # trace side: the step span is annotated and a guard instant fired
    (step,) = [s for s in buf.spans() if s["cat"] == "step"]
    assert step["args"]["demoted"] == "exception"
    (inst,) = buf.instants("guard")
    assert inst["name"] == "demote:linear"
    assert inst["args"] == {"scheme": "f32", "reason": "exception"}
    # the instant fired inside the step's time window
    assert step["ts"] <= inst["ts"] <= step["ts"] + step["dur"]
