"""Observability subsystem (`repro.obs`): metrics-registry semantics
(types, label pinning, bounded reservoirs, exporters, state transplant),
structured tracing (span nesting with an injectable clock, Chrome-trace
validity, the disabled-mode fast path), the plan profiler, and the wiring
through the executor / pass manager / serving scheduler -- per-step spans
match plan step count for every demo app, and a serving trace links every
completed request to exactly one macro-batch span."""

import contextlib
import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import compile_plan, optimize
from repro.core.graph.pass_manager import PassManager
from repro.models.cnn import APPS, app_masks
from repro.obs import metrics, profile_plan, trace
from repro.obs.metrics import MetricsRegistry
from repro.serving import AsyncPlanServer

KEY = jax.random.PRNGKey(0)


def _plan(app="super_resolution", backend="reference"):
    g = APPS[app](KEY, base=8)
    masks, structures = app_masks(g, app, sparsity=0.5)
    go = optimize(g, masks, structures)
    return go, compile_plan(go, backend=backend)


def _frame(app, i=0, size=8):
    c = 1 if app == "coloring" else 3
    return jax.random.normal(jax.random.PRNGKey(i), (c, size, size))


# --------------------------------------------------------------------------- #
# metrics registry                                                             #
# --------------------------------------------------------------------------- #


def test_counter_semantics():
    r = MetricsRegistry()
    c = r.counter("hits_total", op="conv2d")
    c.inc()
    c.inc(4)
    assert c.value == 5
    # same (name, labels) resolves to the same series
    assert r.counter("hits_total", op="conv2d").value == 5
    assert r.counter("hits_total", op="linear").value == 0
    with pytest.raises(ValueError):
        c.inc(-1)  # counters are monotonic


def test_gauge_set_max_keeps_high_water():
    r = MetricsRegistry()
    g = r.gauge("queue_peak", plan="sr")
    g.set_max(3)
    g.set_max(1)  # lower: ignored
    assert g.value == 3
    g.set(0.5)  # plain set overwrites
    assert g.value == 0.5
    g.add(2)
    assert g.value == 2.5


def test_histogram_reservoir_is_bounded_but_totals_exact():
    r = MetricsRegistry()
    h = r.histogram("lat_ms", reservoir=100, plan="sr")
    for i in range(1000):
        h.observe(float(i))
    assert h.count == 1000  # exact over every observation
    assert h.sum == sum(range(1000))
    # percentiles come from the most recent 100 observations only
    assert h.percentile(0) >= 900
    assert h.percentile(100) == 999
    s = h.stats()
    assert s["count"] == 1000 and 900 <= s["p50"] <= 999
    assert s["p95"] >= s["p50"] and s["p99"] >= s["p95"]


def test_type_collision_raises():
    r = MetricsRegistry()
    r.counter("x_total")
    with pytest.raises(ValueError, match="one name, one type"):
        r.gauge("x_total")
    with pytest.raises(ValueError, match="one name, one type"):
        r.histogram("x_total")


def test_label_names_pinned_per_family():
    r = MetricsRegistry()
    r.counter("y_total", op="conv2d", scheme="w8")
    # same names, different values: fine (new series)
    r.counter("y_total", op="linear", scheme="f32").inc()
    with pytest.raises(ValueError, match="pinned"):
        r.counter("y_total", op="conv2d")  # missing a label name
    with pytest.raises(ValueError, match="pinned"):
        r.counter("y_total", op="conv2d", backend="kernel", scheme="w8")


def test_label_counts_view_matches_legacy_shape():
    r = MetricsRegistry()
    r.counter("demote_total", op="conv2d", scheme="w8", reason="numeric").inc(2)
    r.counter("demote_total", op="linear", scheme="f32", reason="exception").inc()
    assert r.label_counts("demote_total", "op", "scheme", "reason") == {
        "conv2d/w8/numeric": 2.0,
        "linear/f32/exception": 1.0,
    }
    assert r.label_counts("unknown_total", "op") == {}


def test_snapshot_json_and_prometheus_exports():
    r = MetricsRegistry()
    r.counter("req_total", help="requests", plan="sr").inc(3)
    r.gauge("depth", plan="sr").set(2)
    h = r.histogram("lat_s", plan='s"r\n')  # exporter must escape this
    h.observe(1.0)
    h.observe(3.0)
    snap = json.loads(r.to_json())
    assert snap["req_total"]["type"] == "counter"
    assert snap["req_total"]["samples"][0] == {
        "labels": {"plan": "sr"}, "value": 3.0,
    }
    hs = snap["lat_s"]["samples"][0]
    assert hs["count"] == 2 and hs["sum"] == 4.0 and hs["p50"] == 2.0
    text = r.to_prometheus()
    assert '# TYPE req_total counter' in text
    assert 'req_total{plan="sr"} 3' in text
    assert '# TYPE lat_s summary' in text
    assert 'lat_s_count{plan="s\\"r\\n"} 2' in text
    assert 'quantile="0.5"' in text
    assert '# HELP req_total requests' in text


def test_dump_load_state_roundtrip_is_exact():
    r = MetricsRegistry()
    r.counter("a_total", k="v").inc(7)
    r.histogram("b_ms", reservoir=8).observe(1.5)
    state = r.dump_state()
    r.counter("a_total", k="v").inc()  # diverge
    r.counter("c_total").inc()  # new family
    r.load_state(state)
    assert r.counter("a_total", k="v").value == 7
    assert "c_total" not in r.names()
    assert r.dump_state() == state
    # the dump is a deep copy: mutating the registry never changes it
    r.histogram("b_ms", reservoir=8).observe(9.9)
    assert state["b_ms"]["series"][()]["reservoir"] == [1.5]


def test_reset_family_keeps_type_pinned():
    r = MetricsRegistry()
    r.counter("z_total", op="a").inc()
    r.reset("z_total")
    assert r.label_counts("z_total", "op") == {}
    with pytest.raises(ValueError):
        r.gauge("z_total")  # family survived: type still pinned


# --------------------------------------------------------------------------- #
# tracing                                                                      #
# --------------------------------------------------------------------------- #


def test_span_nesting_with_injected_clock():
    t = [0.0]

    def clock():
        t[0] += 0.001  # 1ms per clock read
        return t[0]

    with trace.tracing(clock) as buf:
        with trace.span("outer", cat="t") as outer:
            with trace.span("inner", cat="t"):
                pass
            outer.set("k", "v")
    spans = buf.spans()
    assert [s["name"] for s in spans] == ["outer", "inner"]
    outer_s, inner_s = spans
    # B(outer)=1ms B(inner)=2ms E(inner)=3ms E(outer)=4ms
    assert outer_s["dur"] == pytest.approx(3000.0)
    assert inner_s["dur"] == pytest.approx(1000.0)
    assert inner_s["ts"] > outer_s["ts"]
    assert inner_s["ts"] + inner_s["dur"] <= outer_s["ts"] + outer_s["dur"]
    assert outer_s["args"] == {"k": "v"}  # set() lands on the begin event


def test_chrome_trace_validity_phases_pair_and_timestamps_monotonic():
    with trace.tracing() as buf:
        with trace.span("a"):
            trace.instant("mark", cat="g", why="test")
        with trace.span("b"):
            pass
    doc = buf.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert json.loads(json.dumps(doc)) == doc  # JSON-serializable as-is
    ts = [ev["ts"] for ev in events]
    assert ts == sorted(ts)  # single-threaded: strictly append-ordered
    assert {ev["ph"] for ev in events} == {"B", "E", "i"}
    assert all({"name", "ph", "pid", "tid", "ts"} <= set(ev) for ev in events)
    buf.spans()  # pairs up: no exception


def test_unbalanced_trace_is_detected():
    buf = trace.TraceBuffer()
    buf.add({"name": "x", "cat": "t", "ph": "B", "pid": 1, "tid": 1, "ts": 0.0,
             "args": {}})
    with pytest.raises(ValueError, match="unclosed"):
        buf.spans()
    buf2 = trace.TraceBuffer()
    buf2.add({"name": "x", "ph": "E", "pid": 1, "tid": 1, "ts": 0.0})
    with pytest.raises(ValueError, match="empty stack"):
        buf2.spans()


def test_span_error_annotated():
    with trace.tracing() as buf:
        with pytest.raises(RuntimeError):
            with trace.span("boom"):
                raise RuntimeError("x")
    (sp,) = buf.spans()
    assert sp["args"]["error"] == "RuntimeError"


def test_disabled_mode_is_allocation_free_and_inert():
    assert not trace.enabled()
    s1 = trace.span("a", op="x")
    s2 = trace.span("b")
    assert s1 is s2 is trace.NULL_SPAN  # one shared singleton, no allocation
    with s1 as sp:
        sp.set("k", "v")  # no-op, no error
    trace.instant("never")
    trace.async_begin("never", 1)
    trace.async_end("never", 1)
    assert trace.current_buffer() is None


def test_tracing_context_restores_previous_session():
    outer = trace.start_tracing()
    try:
        trace.instant("outer-1")
        with trace.tracing() as inner:
            trace.instant("inner-1")
            assert trace.current_buffer() is inner
        assert trace.current_buffer() is outer  # nested session composes
        trace.instant("outer-2")
        assert [e["name"] for e in outer.instants()] == ["outer-1", "outer-2"]
        assert [e["name"] for e in inner.instants()] == ["inner-1"]
    finally:
        trace.stop_tracing()


def test_async_events_cross_thread_ids():
    with trace.tracing() as buf:
        trace.async_begin("request", 7, cat="serving", plan="sr")

        def worker():
            trace.async_instant("request", 7, cat="serving", phase="batched")

        th = threading.Thread(target=worker)
        th.start()
        th.join()
        trace.async_end("request", 7, cat="serving")
    evs = buf.async_events("request")
    assert [e["ph"] for e in evs] == ["b", "n", "e"]
    assert {e["id"] for e in evs} == {"7"}  # one logical op across threads
    assert len({e["tid"] for e in evs}) == 2


# --------------------------------------------------------------------------- #
# executor / pass-manager wiring                                               #
# --------------------------------------------------------------------------- #


def test_jitted_plan_emits_no_plan_or_step_spans():
    go, plan = _plan("coloring")
    x = _frame("coloring")[None]
    with trace.tracing() as buf:
        jax.jit(plan)(go.params, x)  # run_steps runs on tracers, once
        assert not buf.spans()
        plan(go.params, x)  # the eager run still times its steps
    assert len([s for s in buf.spans() if s["cat"] == "step"]) == len(plan.steps)


def test_plan_steps_name_the_ops_they_lower_to():
    go, plan = _plan("coloring")
    hlo = jax.jit(plan).lower(go.params, _frame("coloring")[None]).compile().as_text()
    scopes = {
        st.node.name for st in plan.steps if f"/{st.node.name}/" in hlo
    }
    convs = {st.node.name for st in plan.steps if st.node.op == "conv2d"}
    assert convs and convs <= scopes


@pytest.mark.parametrize("app", sorted(APPS))
def test_per_step_spans_match_plan_step_count(app):
    go, plan = _plan(app)
    x = _frame(app)[None]
    with trace.tracing() as buf:
        y = plan(go.params, x)
    steps = [s for s in buf.spans() if s["cat"] == "step"]
    assert len(steps) == len(plan.steps)
    assert [s["name"] for s in steps] == [st.node.name for st in plan.steps]
    for s in steps:
        assert s["args"]["backend"] == "reference"
        assert s["args"]["op"]
        assert s["args"]["out_shape"]
    (plan_span,) = [s for s in buf.spans() if s["cat"] == "plan"]
    assert plan_span["args"]["steps"] == len(plan.steps)
    # parity: the traced run computes exactly what the untraced run does
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(plan(go.params, x)), rtol=1e-6, atol=1e-6
    )


def test_untraced_run_emits_nothing():
    go, plan = _plan("coloring")
    with trace.tracing() as buf:
        pass  # session closed before the run
    plan(go.params, _frame("coloring")[None])
    assert len(buf) == 0


def test_pass_manager_emits_per_pass_spans():
    g = APPS["coloring"](KEY, base=8)
    masks, structures = app_masks(g, "coloring", sparsity=0.5)
    from repro.core.graph.pass_manager import PassContext

    pm = PassManager()
    with trace.tracing() as buf:
        pm.run(g, PassContext(masks=masks, structures=structures))
    passes = [s for s in buf.spans() if s["cat"] == "pass"]
    # skipped passes (needs_calibration without a table) emit no span
    assert [s["name"] for s in passes] == [
        p.name for p in pm.passes if p.name != "quantize"
    ]
    for s in passes:
        assert s["args"]["nodes_before"] >= s["args"]["nodes_after"] or True
        assert "changed" in s["args"]


def test_guard_demotions_hit_registry_and_spans():
    from repro.core.graph import guard_fallback_counts
    from repro.robustness import FaultPlan, FaultRule

    go, plan = _plan("coloring", backend="guarded")
    x = _frame("coloring")[None]
    before = sum(guard_fallback_counts().values())
    with FaultPlan([FaultRule("conv2d", "raise", rate=1.0)]):
        with trace.tracing() as buf:
            plan(go.params, x)
    # registry view: demotions counted under op/scheme/reason
    counts = guard_fallback_counts()
    n_conv = sum(v for k, v in counts.items() if k.startswith("conv2d/"))
    assert n_conv >= 1 and sum(counts.values()) > before
    # span annotations: demoted steps carry the reason + a guard instant
    demoted = [
        s for s in buf.spans()
        if s["cat"] == "step" and s["args"].get("demoted")
    ]
    assert len(demoted) >= 1
    # first few steps demote on the raised fault; once the breaker trips,
    # the rest demote pre-emptively with breaker_open
    reasons = {s["args"]["demoted"] for s in demoted}
    assert "exception" in reasons
    assert reasons <= {"exception", "breaker_open"}
    instants = buf.instants("guard")
    assert len(instants) == len(demoted)  # one guard instant per demoted step
    assert all(i["name"].startswith("demote:") for i in instants)
    assert [i["args"]["reason"] for i in instants] == [
        s["args"]["demoted"] for s in demoted
    ]


def test_conv_fallback_counts_are_registry_views():
    from repro.kernels import ops as kops

    x = jnp.ones((1, 4, 6, 6))
    w = jnp.ones((4, 2, 3, 3))
    kops.conv2d(x, w, groups=2, interpret=True)
    assert kops.conv_fallback_counts().get("groups", 0) >= 1
    raw = metrics.registry().label_counts("conv_fallback_total", "reason")
    assert raw.get("groups", 0) >= 1
    kops.reset_conv_fallbacks()
    assert kops.conv_fallback_counts() == {}


# --------------------------------------------------------------------------- #
# profiler                                                                     #
# --------------------------------------------------------------------------- #


def test_profile_plan_rows_match_steps():
    go, plan = _plan("super_resolution")
    x = _frame("super_resolution")[None]
    prof = profile_plan(plan, go.params, x, runs=2, warmup=1)
    assert prof.backend == "reference"
    assert len(prof.steps) == len(plan.steps)
    assert prof.runs == 2
    assert prof.total_ms > 0
    assert sum(s.pct for s in prof.steps) == pytest.approx(100.0)
    for row, st in zip(prof.steps, plan.steps):
        assert row.name == st.node.name and row.op == st.node.op
        assert row.ms >= 0 and row.bytes_moved > 0
        assert row.attribution == "reference"
        assert row.out_shape
    text = prof.render_text(top=3)
    assert "plan profile" in text and text.count("\n") == 4  # header+head+3
    blob = json.dumps(prof.to_json())
    assert json.loads(blob)["backend"] == "reference"
    # the profiler restores the caller's tracing state (off)
    assert not trace.enabled()


def test_profile_plan_trace_is_valid_chrome_trace(tmp_path):
    go, plan = _plan("coloring")
    prof = profile_plan(plan, go.params, _frame("coloring")[None], runs=1)
    p = prof.trace.save(str(tmp_path / "t.json"))
    doc = json.load(open(p))
    assert doc["displayTimeUnit"] == "ms"
    steps = [s for s in prof.trace.spans() if s["cat"] == "step"]
    assert len(steps) == len(plan.steps)  # one span per plan step


# --------------------------------------------------------------------------- #
# serving wiring                                                               #
# --------------------------------------------------------------------------- #


def _sr_server(**kw):
    go, plan = _plan("super_resolution")
    server = AsyncPlanServer(clock=kw.pop("clock", lambda: 0.0), **kw)
    server.add_plan("sr", plan, go.params, batch_size=2)
    return server


def test_serving_trace_links_requests_to_exactly_one_batch():
    server = _sr_server()
    with trace.tracing() as buf:
        handles = [
            server.submit("sr", _frame("super_resolution", i)) for i in range(6)
        ]
        while server.step():
            pass
        assert all(h.done() for h in handles)
        server.close()
    batch_spans = [s for s in buf.spans() if s["name"] == "batch"]
    assert len(batch_spans) == 3  # 6 requests / batch_size 2
    # every rid appears in exactly one batch span's membership
    rid_to_batch = {}
    for s in batch_spans:
        for rid in s["args"]["rids"]:
            assert rid not in rid_to_batch
            rid_to_batch[rid] = s["args"]["batch"]
    assert sorted(rid_to_batch) == [h.rid for h in handles]
    # and the request's own async events agree with the batch that served it
    for h in handles:
        evs = buf.async_events("request")
        mine = [e for e in evs if e["id"] == str(h.rid)]
        phases = [e["ph"] for e in mine]
        assert phases == ["b", "n", "e"]  # submit -> batched -> completed
        batched = [e for e in mine if e["ph"] == "n"][0]
        done = [e for e in mine if e["ph"] == "e"][0]
        assert batched["args"]["batch"] == rid_to_batch[h.rid]
        assert done["args"]["phase"] == "completed"
        assert done["args"]["deadline_missed"] is False


def test_serving_stats_mirrored_into_registry():
    server = _sr_server()
    for i in range(4):
        server.submit("sr", _frame("super_resolution", i))
    while server.step():
        pass
    server.close()
    events = metrics.registry().label_counts(
        "serving_events_total", "plan", "event"
    )
    assert events["sr/submitted"] == 4
    assert events["sr/completed"] == 4
    assert events["sr/batches"] == 2
    lat = metrics.registry().histogram("serving_latency_seconds", plan="sr")
    assert lat.count == 4
    peak = metrics.registry().gauge("serving_queue_depth_peak", plan="sr")
    assert peak.value == 4  # all four queued before the first tick
    assert server.health()["plans"]["sr"]["queue_peak"] == 4


def test_shed_request_ends_its_trace_span():
    server = _sr_server(max_queue=1, overload="shed")
    with trace.tracing() as buf:
        h1 = server.submit("sr", _frame("super_resolution", 0))
        h2 = server.submit(
            "sr", _frame("super_resolution", 1), priority=1
        )  # evicts h1
        evs = [e for e in buf.async_events("request") if e["id"] == str(h1.rid)]
        assert [e["ph"] for e in evs] == ["b", "e"]
        assert evs[-1]["args"]["phase"] == "shed"
        server.step(force=True)
        server.close()
    assert h2.done()


# --------------------------------------------------------------------------- #
# the served host path: submit, batch, chunk                                   #
# --------------------------------------------------------------------------- #


def _ticks():
    t = [0.0]

    def clock():
        t[0] += 1e-6  # 1us per read: every span has a length, none ties
        return t[0]

    return clock


def _inside(outer, spans):
    """The spans on ``outer``'s thread that lie strictly inside it."""
    end = outer["ts"] + outer["dur"]
    return [
        s for s in spans
        if s is not outer and s["tid"] == outer["tid"]
        and outer["ts"] < s["ts"] and s["ts"] + s["dur"] < end
    ]


def _children(outer, spans):
    """The spans directly inside ``outer``, in start order."""
    inner = _inside(outer, spans)
    return [s for s in inner if not any(s in _inside(o, inner) for o in inner)]


def _served(n, **kw):
    """``n`` frames submitted, then served by synchronous ticks (the last,
    partial batch forced) under a tracing session on an injected clock."""
    server = _sr_server(**kw)
    with trace.tracing(_ticks()) as buf:
        handles = [
            server.submit("sr", _frame("super_resolution", i)) for i in range(n)
        ]
        while server.step():
            pass
        server.step(force=True)
        server.close()
    assert all(h.done() for h in handles)
    return handles, buf.spans()


def test_batch_spans_hold_one_chunk_call_between_stack_and_resolve():
    _, spans = _served(5)  # batch 2: two full batches and one padded
    batches = [s for s in spans if s["name"] == "batch"]
    assert [len(b["args"]["rids"]) for b in batches] == [2, 2, 1]
    for b in batches:
        kids = [s["name"] for s in _children(b, spans)]
        padded = len(b["args"]["rids"]) < 2
        assert kids == (
            ["batch.stack"] + ["chunk.pad"] * padded
            + ["chunk.call", "chunk.slice", "batch.resolve"]
        )
        assert [s["name"] for s in _inside(b, spans)].count("chunk.call") == 1
        (resolve,) = [s for s in _children(b, spans) if s["name"] == "batch.resolve"]
        assert [s["name"] for s in _children(resolve, spans)] == ["batch.lock"]
    # the chunk is jitted: its run on tracers emitted no plan/step spans
    assert not [s for s in spans if s["name"] == "plan" or s["cat"] == "step"]


def test_submit_spans_hold_lock_and_copy_and_carry_the_rid():
    handles, spans = _served(3)
    submits = [s for s in spans if s["name"] == "submit"]
    assert [s["args"]["rid"] for s in submits] == [h.rid for h in handles]
    for s in submits:
        assert s["cat"] == "serving"
        assert [c["name"] for c in _children(s, spans)] == [
            "submit.lock", "submit.to_device",
        ]


def test_disabled_serving_path_builds_no_span_and_no_args(monkeypatch):
    assert not trace.enabled()
    server = _sr_server()
    calls = []
    real = trace.span

    def spy(name, cat="repro", **args):
        calls.append((name, args))
        return real(name, cat, **args)

    def refuse(*a, **k):
        raise AssertionError("a live span was built with tracing off")

    monkeypatch.setattr(trace, "span", spy)
    monkeypatch.setattr(trace._Span, "__init__", refuse)
    handles = [server.submit("sr", _frame("super_resolution", i)) for i in range(3)]
    while server.step():
        pass
    server.step(force=True)
    server.close()
    assert all(h.done() for h in handles)
    names = {n for n, _ in calls}
    assert {
        "submit", "submit.lock", "submit.to_device", "batch.stack",
        "chunk.pad", "chunk.call", "chunk.slice", "batch.resolve", "batch.lock",
    } <= names
    assert "batch" not in names  # its rids list is never built
    assert all(args == {} for _, args in calls)
    assert trace.current_buffer() is None


def _profiled(how, logdir):
    """A profiler session over a traced block: opened by hand, or as
    ``launch/serve.py --profile-dir`` opens it."""
    if how == "start_trace":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        stack = contextlib.ExitStack()
        stack.enter_context(jax.profiler.trace(logdir, profiler_options=opts))
        stack.enter_context(trace.tracing())
        return stack
    from types import SimpleNamespace

    from repro.launch.serve import _telemetry

    return _telemetry(SimpleNamespace(
        profile_dir=logdir, metrics_dump=None, metrics_interval=0.5,
    ))


@pytest.mark.parametrize("how", ["start_trace", "serve_profile_dir"])
def test_spans_land_in_the_profiler_trace_on_a_host_plane(tmp_path, how):
    from jax.profiler import ProfileData

    server = _sr_server()
    warm = [server.submit("sr", _frame("super_resolution", i)) for i in range(2)]
    server.step()  # compile outside the profiled session
    assert all(h.done() for h in warm)
    with _profiled(how, str(tmp_path)):
        assert trace.enabled()
        hs = [server.submit("sr", _frame("super_resolution", i)) for i in range(2)]
        server.step()
    assert not trace.enabled()
    server.close()
    run = os.path.join(str(tmp_path), "plugins", "profile", "*")
    (path,) = glob.glob(os.path.join(run, "*.xplane.pb"))
    if how == "serve_profile_dir":  # and a file Perfetto opens
        assert glob.glob(os.path.join(run, "perfetto_trace.json.gz"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("batch", "submit", "chunk.call"):
                    stats = {k: v for k, v in ev.stats}
                    found.setdefault(ev.name, []).append((plane.name, stats))
    (plane, stats), = found["batch"]
    assert plane.startswith("/host:")
    assert stats["plan"] == "sr" and stats["reason"] == "full"
    assert stats["batch"] == 1  # the second batch this server ran
    assert "rids" not in stats  # a list: the buffer keeps it, not the annotation
    assert sorted(st["rid"] for _, st in found["submit"]) == [h.rid for h in hs]
    assert len(found["chunk.call"]) == 1
