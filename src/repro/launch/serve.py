"""Serving launcher: batched generation + continuous-batching demo, plus
plan-based serving of the paper's three vision apps.

Examples (CPU):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --batch 4 --prompt-len 16 --new-tokens 12
  PYTHONPATH=src python -m repro.launch.serve --llm --smoke --frames 6 \
      --new-tokens 8          # decoder plans + paged KV continuous batching
  PYTHONPATH=src python -m repro.launch.serve --graph-app style_transfer \
      --size 64 --frames 3
  PYTHONPATH=src python -m repro.launch.serve --graph-app coloring \
      --size 64 --frames 10 --batch-size 4   # throughput mode (PlanServer)
  PYTHONPATH=src python -m repro.launch.serve --graph-app style_transfer \
      --quantize                             # INT8 weights + parity stats
  PYTHONPATH=src python -m repro.launch.serve --async --frames 8 \
      --batch-size 4 --flush-after 0.01      # all three apps, one process
"""

from __future__ import annotations

import argparse
import contextlib
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config, smoke_config
from ..models import get_model
from ..serving.engine import Engine, Request, RequestScheduler
from ..utils.compile_cache import enable_compile_cache
from ..utils.fileio import atomic_write_json
from . import parity


class _MetricsDump:
    """``--metrics-dump`` session: arms tracing for the duration, snapshots
    the metrics registry every ``interval`` seconds on a daemon thread, and
    on exit writes the snapshot series (plus a final one) to ``path`` and
    the session's Chrome trace next to it (``<path>.trace.json``)."""

    def __init__(self, path: str, interval: float):
        self.path = path
        self.interval = interval
        self._snaps: list = []
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def _loop(self) -> None:
        from ..obs import metrics

        while not self._stop.wait(self.interval):
            self._snaps.append(
                {"t": time.time(), "metrics": metrics.registry().snapshot()}
            )

    def __enter__(self) -> "_MetricsDump":
        from ..obs import trace

        trace.start_tracing()
        self._thread = threading.Thread(
            target=self._loop, name="metrics-dump", daemon=True
        )
        self._thread.start()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        from ..obs import metrics, trace

        self._stop.set()
        self._thread.join()
        self._snaps.append(
            {"t": time.time(), "metrics": metrics.registry().snapshot()}
        )
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        # crash-safe (utils.fileio): a killed server never leaves a
        # truncated snapshot JSON -- same recipe as TuningCache.save
        atomic_write_json(
            self.path,
            {"interval_s": self.interval, "snapshots": self._snaps},
            indent=1, prefix=".metrics-",
        )
        buf = trace.stop_tracing()
        trace_path = buf.save(self.path + ".trace.json")
        print(f"metrics: {len(self._snaps)} snapshots -> "
              f"{os.path.abspath(self.path)}")
        print(f"trace: {len(buf.events)} events -> {trace_path} "
              f"(load in Perfetto / chrome://tracing)")


def _telemetry(args) -> contextlib.ExitStack:
    """The serving session's telemetry: ``--profile-dir`` runs the JAX
    profiler over it with tracing armed (the program's spans land in the
    profiler's trace beside the device's operations); ``--metrics-dump``
    adds registry snapshots and the buffer's Chrome trace.  Tracing stops
    before the profiler does, so every span is closed in its trace."""
    from ..obs import trace

    stack = contextlib.ExitStack()
    if args.profile_dir:
        stack.enter_context(
            jax.profiler.trace(args.profile_dir, create_perfetto_trace=True)
        )
    if args.metrics_dump:
        stack.enter_context(_MetricsDump(args.metrics_dump, args.metrics_interval))
    elif args.profile_dir:
        stack.enter_context(trace.tracing())
    return stack


def _pick_backend(args) -> str:
    """The plan backend this run serves on, printed as its first line: the
    Pallas kernels on a TPU (``quant`` under ``--quantize``), the jnp
    ``reference`` backend elsewhere -- interpret-mode Pallas on a CPU would
    time Python, not the model.  ``--guarded`` (async / llm) serves the
    guarded backend on any platform; the default LM demo runs the model's
    jnp forward through :class:`Engine`."""
    platform = jax.default_backend()
    if not (args.graph_app or args.async_serve or args.llm):
        backend = "engine"
    elif args.guarded and (args.async_serve or args.llm):
        backend = "guarded"
    elif platform != "tpu":
        backend = "reference"
    else:
        backend = "quant" if args.quantize and not args.async_serve else "kernel"
    print(f"serve: platform={platform} backend={backend}")
    return backend


def _serve_graph_app(args) -> None:
    """Compile one of the paper's demo apps through the full pipeline
    (PassManager -> execution plan) and serve frames through the plan."""
    from ..core.graph import PassContext, PassManager, compile_plan
    from ..models.cnn import APP_ACT_SKIP, APP_QUANT_SKIP, APPS, app_masks

    build = APPS[args.graph_app]
    g = build(jax.random.PRNGKey(args.seed), base=args.base)
    masks, structures = app_masks(g, args.graph_app, sparsity=args.sparsity)
    ctx = PassContext(masks=masks, structures=structures)
    pm = PassManager()
    go = pm.run(g, ctx)
    print(pm.summary(ctx))

    backend = args.backend
    c_in = 1 if args.graph_app == "coloring" else 3
    shape = (args.batch, c_in, args.size, args.size)
    rng = np.random.default_rng(args.seed)

    if args.quantize:
        # calibrate on the fp32 reference plan, run the quantize pass, and
        # serve the INT8 plan (the quant backend executes qlinear through the
        # INT8 Pallas kernels; on CPU the jnp dequant reference serves)
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
        gq = PassManager(("quantize",)).run(go, qctx)
        plan = compile_plan(gq, backend=backend)
        # plan-level parity + storage stats vs the fp32 reference plan
        probe = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        err = jnp.max(jnp.abs(jnp.asarray(plan(gq.params, probe))
                              - jnp.asarray(plan_f32(go.params, probe))))
        mem_f = plan_f32.memory_estimate(jax.ShapeDtypeStruct(shape, jnp.float32))
        mem_q = plan.memory_estimate(jax.ShapeDtypeStruct(shape, jnp.float32))
        print(
            f"quantize: calibrated {table.batches} batches over "
            f"{len(table.ranges)} values; max_abs_err={float(err):.2e} "
            f"weights {mem_f['param_bytes'] / 1e6:.2f}MB -> "
            f"{mem_q['param_bytes'] / 1e6:.2f}MB "
            f"({mem_f['param_bytes'] / mem_q['param_bytes']:.2f}x, "
            f"{mem_q['weight_bytes_saved'] / 1e6:.2f}MB saved)"
        )
        go = gq
    else:
        plan = compile_plan(go, backend=backend)

    mem = plan.memory_estimate(jax.ShapeDtypeStruct(shape, jnp.float32))
    print(
        f"plan: backend={backend} steps={len(plan.steps)} "
        f"peak_act={mem['peak_activation_bytes'] / 1e6:.2f}MB "
        f"params={mem['param_bytes'] / 1e6:.2f}MB"
    )

    if args.batch_size is not None:
        # throughput mode: a queue of single frames served in fixed-size
        # compiled batches (tail batch padded, never re-compiled)
        from ..serving.engine import PlanServer

        server = PlanServer(plan, go.params, args.batch_size)
        n_frames = args.frames * args.batch
        # warm the chunk compilation before timing
        server.submit(jnp.zeros((c_in, args.size, args.size), jnp.float32))
        jax.block_until_ready(server.flush())
        server.stats = {k: 0 for k in server.stats}
        for _ in range(n_frames):
            server.submit(
                jnp.asarray(
                    rng.standard_normal((c_in, args.size, args.size)), jnp.float32
                )
            )
        t0 = time.time()
        jax.block_until_ready(server.flush())
        dt = time.time() - t0
        s = server.stats
        print(
            f"{args.graph_app}: {s['frames']} frames in {dt:.3f}s "
            f"({s['frames'] / dt:.1f} frames/s) over {s['batches']} batches "
            f"of {args.batch_size} ({s['padded_frames']} padded)"
        )
        return

    f = jax.jit(plan)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    jax.block_until_ready(f(go.params, x))  # compile
    times = []
    for _ in range(args.frames):
        t0 = time.time()
        jax.block_until_ready(f(go.params, x))
        times.append(time.time() - t0)
    ms = float(np.median(times)) * 1e3
    print(f"{args.graph_app}: {ms:.2f} ms/frame over {args.frames} frames "
          f"({shape[0]}x{shape[2]}x{shape[3]}, sparsity {args.sparsity})")


def _parse_tenants(spec: str):
    """Parse ``--tenants`` specs: comma-separated
    ``name[:weight[:rate[:burst]]]`` (weight = fair share of batch slots,
    rate/burst = token-bucket quota in requests/s)."""
    out = []
    for part in spec.split(","):
        bits = [b.strip() for b in part.strip().split(":")]
        if not bits or not bits[0]:
            raise SystemExit(f"--tenants: empty tenant name in {spec!r}")
        out.append((
            bits[0],
            float(bits[1]) if len(bits) > 1 else 1.0,
            float(bits[2]) if len(bits) > 2 else None,
            float(bits[3]) if len(bits) > 3 else None,
        ))
    return out


def _serve_async(args) -> None:
    """One AsyncPlanServer process hosting every demo app (or just
    ``--graph-app``): compile each app's plan, start the tick-driven
    scheduler thread, drive mixed traffic with per-request deadlines, and
    report throughput, p50/p95 latency, deadline-miss and padding stats --
    with a per-app parity probe vs direct plan execution.  With
    ``--tenants`` the traffic is spread round-robin over the registered
    tenants (weighted fair share + quotas) and the report breaks latency,
    throttling, and ladder state out per tenant."""
    from ..core.graph import PassContext, PassManager, compile_plan
    from ..models.cnn import APPS, app_masks
    from ..serving import AsyncPlanServer, submit_with_retry

    if args.quantize:
        raise SystemExit(
            "--async serves f32 plans only (for INT8 serving use "
            "--graph-app <app> --quantize); refusing to silently ignore "
            "--quantize"
        )
    apps = [args.graph_app] if args.graph_app else list(APPS)
    # --guarded serves degradation-tolerant plans: each step tries the
    # kernel/quant handler and demotes failures to the jnp reference (with
    # circuit breakers + numeric guards); stats land in server.health()
    backend = args.backend
    batch_size = args.batch_size or 4
    rng = np.random.default_rng(args.seed)

    server = AsyncPlanServer(
        flush_after=args.flush_after, max_queue=args.max_queue,
        overload=args.overload, watchdog=args.watchdog,
    )
    tenant_specs = _parse_tenants(args.tenants) if args.tenants else []
    tnames = [t[0] for t in tenant_specs]
    for name, weight, rate, burst in tenant_specs:
        server.add_tenant(name, weight=weight, rate=rate, burst=burst)
        quota = f"{rate}/s" if rate is not None else "unlimited"
        print(f"async: tenant {name}: weight={weight} quota={quota}")
    plans, shapes = {}, {}
    for app in apps:
        g = APPS[app](jax.random.PRNGKey(args.seed), base=args.base)
        masks, structures = app_masks(g, app, sparsity=args.sparsity)
        go = PassManager().run(g, PassContext(masks=masks, structures=structures))
        plan = compile_plan(go, backend=backend)
        plans[app] = (plan, go.params)
        c_in = 1 if app == "coloring" else 3
        shapes[app] = (c_in, args.size, args.size)
        # explicit input spec: a malformed frame fails at submit(), never
        # inside the macro-batch it would have joined
        server.add_plan(
            app, plan, go.params, batch_size,
            input_spec=[(shapes[app], jnp.float32)],
        )
        print(f"async: {app}: backend={backend} steps={len(plan.steps)} "
              f"batch_size={batch_size}")

    with server:
        server.start()
        # warm each app's chunk compilation before timing; snapshot the
        # counters after it so the report covers the traffic window only
        for app in apps:
            server.submit(app, jnp.zeros(shapes[app], jnp.float32)).result()
        warm = server.stats
        n = args.frames * args.batch
        handles, probes = [], {}
        t0 = time.time()
        for i in range(n):
            app = apps[i % len(apps)]
            x = jnp.asarray(rng.standard_normal(shapes[app]), jnp.float32)
            tenant = tnames[i % len(tnames)] if tnames else None
            # with quotas in play, ride out QuotaExceededError via the
            # shared jittered backoff instead of failing the demo
            h = submit_with_retry(
                server, app, x, priority=i % 2, deadline=args.deadline,
                tenant=tenant,
            )
            handles.append(h)
            probes.setdefault(app, (x, h))  # first frame per app: parity probe
        for h in handles:
            h.result()
        dt = time.time() - t0
        for app, (x, h) in probes.items():
            # async path == direct execution, within the platform's bound
            plan, params = plans[app]
            with jax.default_matmul_precision("highest"):
                want = np.asarray(plan(params, x[None]))[0]
            err, bound = parity.frame_error(h.result(), want, jax.default_backend())
            assert err <= bound, (app, err, bound)
        s = server.stats
        print(f"async: {len(handles)} requests over {len(apps)} plans in "
              f"{dt:.3f}s ({len(handles) / dt:.1f} req/s), "
              f"{s['batches'] - warm['batches']} batches "
              f"({s['padded_frames'] - warm['padded_frames']} padded frames, "
              f"{s['deadline_flushes'] - warm['deadline_flushes']} deadline "
              f"flushes, {s['deadline_misses'] - warm['deadline_misses']} "
              f"deadline misses, parity ok)")
        for app in apps:
            # percentiles over the traffic handles only: the per-plan
            # reservoirs also hold the warmup request, whose latency is the
            # jit compile, not serving
            lats = np.asarray([h.latency for h in handles if h.plan == app])
            if not lats.size:  # fewer requests than apps: no traffic here
                print(f"async: {app}: no traffic")
                continue
            print(f"async: {app}: p50={np.percentile(lats, 50) * 1e3:.2f}ms "
                  f"p95={np.percentile(lats, 95) * 1e3:.2f}ms "
                  f"p99={np.percentile(lats, 99) * 1e3:.2f}ms "
                  f"over {lats.size} requests")
        if tnames:
            per_tenant = s["per_tenant"]
            for name in tnames:
                lats = np.asarray(
                    [h.latency for h in handles if h.tenant == name]
                )
                st = per_tenant[name]
                if lats.size:
                    pct = (f"p50={np.percentile(lats, 50) * 1e3:.2f}ms "
                           f"p95={np.percentile(lats, 95) * 1e3:.2f}ms "
                           f"p99={np.percentile(lats, 99) * 1e3:.2f}ms "
                           f"over {lats.size} requests, ")
                else:
                    pct = "no traffic, "
                print(f"async: tenant {name}: {pct}"
                      f"throttled={st['throttled']} "
                      f"ladder_shed={st['ladder_shed']} "
                      f"deadline_misses={st['deadline_misses']}")
        # liveness/degradation snapshot: what an external monitor scrapes
        health = server.health()
        print(f"health: running={health['running']} "
              f"inflight={health['inflight']} pending={health['pending']} "
              f"tick_errors={health['tick_errors']} "
              f"watchdog={health['watchdog']}")
        for app, p in health["plans"].items():
            s = p["stats"]
            line = (f"health: {app}: queue_depth={p['queue_depth']} "
                    f"queue_peak={p['queue_peak']} "
                    f"bad_frames={s['bad_frames']} "
                    f"watchdog_timeouts={s['watchdog_timeouts']} "
                    f"rejected={s['rejected']} shed={s['shed']}")
            if "guard" in p:
                gc = p["guard"]["counters"]
                brs = ", ".join(
                    f"{k}={b['state']}" for k, b in p["guard"]["breakers"].items()
                )
                line += (f" | guard: primary_ok={gc['primary_ok']} "
                         f"fallbacks={gc['fallbacks']} "
                         f"breakers=[{brs or 'none yet'}]")
            print(line)
        for name in tnames:
            th = health["tenants"][name]
            print(f"health: tenant {name}: level={th['level_name']} "
                  f"weight={th['weight']} tokens={th['tokens']}")


def _serve_llm(args) -> None:
    """Serve an autoregressive decoder through the plan compiler: lower the
    model to prefill/decode graphs (``build_decoder_graph``), run the
    PassManager pipeline, compile both plans, and stream prompts through
    :meth:`AsyncPlanServer.submit_llm` -- token-level continuous batching
    over a paged KV-cache, with a greedy-parity probe vs the plain jnp
    forward loop."""
    from ..core.graph import compile_plan
    from ..core.graph.passes import optimize
    from ..models.transformer import init_lm
    from ..models.transformer_graph import build_decoder_graph, decoder_cache_spec
    from ..serving import AsyncPlanServer, PagedKVCache

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    backend = args.backend
    interpret = backend != "reference" and jax.default_backend() != "tpu"

    go_pre = optimize(build_decoder_graph(params, cfg, phase="prefill"))
    go_dec = optimize(build_decoder_graph(params, cfg, phase="decode"))
    plan_pre = compile_plan(go_pre, backend=backend, interpret=interpret)
    plan_dec = compile_plan(go_dec, backend=backend, interpret=interpret)
    print(f"llm: {args.arch}{' (smoke)' if args.smoke else ''}: "
          f"backend={backend} prefill_steps={len(plan_pre.steps)} "
          f"decode_steps={len(plan_dec.steps)}")

    cache = PagedKVCache(
        num_pages=args.kv_pages, page_size=args.kv_page_size,
        **decoder_cache_spec(cfg),
    )
    rng = np.random.default_rng(args.seed)
    n_seq = max(1, args.frames)
    prompts = [
        rng.integers(0, cfg.vocab, size=int(rng.integers(4, args.prompt_len + 1)))
        .astype(np.int32)
        for _ in range(n_seq)
    ]

    server = AsyncPlanServer(max_queue=args.max_queue)
    server.add_llm(
        "lm", prefill=plan_pre, decode=plan_dec, cache=cache,
        max_batch=args.batch,
    )
    with server:
        server.start()
        t0 = time.time()
        handles = [
            server.submit_llm("lm", p, max_new_tokens=args.new_tokens)
            for p in prompts
        ]
        for h in handles:
            h.result()
        dt = time.time() - t0
    st = server.stats["per_llm"]["lm"]
    toks = sum(len(h.result()) for h in handles)
    print(f"llm: {len(handles)} sequences, {toks} tokens in {dt:.3f}s "
          f"({toks / dt:.1f} tok/s) -- {st['prefill_batches']} prefill + "
          f"{st['decode_batches']} decode batches, "
          f"{st['decode_tokens']} batched decode tokens, "
          f"failed={st['failed']}")
    occ = cache.occupancy()
    print(f"llm: cache {occ['num_pages']}x{occ['page_size']} pages: "
          f"peak_used={occ['peak_used']} leaked={occ['used_pages']}")
    cache.check_invariants()

    # greedy-parity probe: the served tokens == greedy decoding of the jnp
    # model (on a TPU, up to near-ties inside the logit bound)
    tol = parity.logit_tolerance(jax.default_backend())
    served = [[int(t) for t in h.result()] for h in handles]
    agree = parity.greedy_agreement(params, cfg, prompts, served, tol)
    assert agree["worst_miss"] == 0.0, agree
    print(f"llm: greedy parity ok ({agree['match']}/{agree['total']} tokens "
          f"match the jnp model, {agree['near_ties']} near-ties within {tol})")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--scheduler", action="store_true", help="continuous batching demo")
    ap.add_argument("--seed", type=int, default=0)
    # decoder-plan serving: prefill/decode graphs + paged KV continuous batching
    ap.add_argument("--llm", action="store_true",
                    help="serve --arch through the plan compiler: decoder "
                         "graphs (prefill + decode) with a paged KV-cache "
                         "and token-level continuous batching "
                         "(AsyncPlanServer.submit_llm)")
    ap.add_argument("--kv-pages", type=int, default=64,
                    help="llm: total pages in the paged KV-cache pool")
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="llm: tokens per KV-cache page")
    # plan-based vision-app serving (the paper's three demos)
    ap.add_argument("--graph-app",
                    choices=["style_transfer", "coloring", "super_resolution"],
                    default=None, help="serve a demo app through an execution plan")
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--size", type=int, default=64, help="graph-app frame size")
    ap.add_argument("--base", type=int, default=16, help="graph-app channel width")
    ap.add_argument("--frames", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=None,
                    help="graph-app throughput mode: serve frames*batch single "
                         "frames through plan.batched(batch_size) (PlanServer)")
    ap.add_argument("--async", dest="async_serve", action="store_true",
                    help="continuous-batching mode: one AsyncPlanServer hosts "
                         "every demo app (or just --graph-app), a background "
                         "scheduler forms macro-batches from the admission "
                         "queues, per-request latency + deadline stats")
    ap.add_argument("--flush-after", type=float, default=0.02,
                    help="async: partial-batch release deadline (seconds the "
                         "oldest queued request may wait for batch fill)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="async: per-request latency budget in seconds "
                         "(late completions count as deadline misses)")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="async: bounded admission queue per plan")
    ap.add_argument("--overload", choices=["reject", "shed"], default="reject",
                    help="async: backpressure policy when a queue is full")
    ap.add_argument("--tenants", nargs="?", default=None,
                    const="gold:3:200,free:1:50",
                    help="async: serve traffic as multiple tenants -- comma-"
                         "separated name[:weight[:rate[:burst]]] specs "
                         "(weight = fair share of batch slots, rate/burst = "
                         "token-bucket quota in req/s); bare --tenants uses "
                         "a demo 3:1 gold/free split with quotas; the report "
                         "adds per-tenant latency/throttle/ladder lines")
    ap.add_argument("--guarded", action="store_true",
                    help="async: serve guarded plans (per-step kernel ->"
                         " reference demotion with circuit breakers and"
                         " NaN/Inf guards; guard stats in health())")
    ap.add_argument("--watchdog", type=float, default=None,
                    help="async: per-batch execution deadline in seconds; a "
                         "batch that blows it fails only its own handles "
                         "(WatchdogTimeout) and the scheduler keeps ticking")
    ap.add_argument("--quantize", action="store_true",
                    help="graph-app: calibrate + quantize the plan to INT8 "
                         "weights (backend='quant' on TPU) and report parity "
                         "vs the fp32 reference plan")
    ap.add_argument("--calib-batches", type=int, default=2,
                    help="sample batches for activation calibration")
    ap.add_argument("--metrics-dump", default=None,
                    help="write periodic metrics-registry snapshots to this "
                         "JSON path and the session's Chrome trace to "
                         "<path>.trace.json (tracing is armed for the run)")
    ap.add_argument("--metrics-interval", type=float, default=0.5,
                    help="seconds between --metrics-dump registry snapshots")
    ap.add_argument("--profile-dir", default=None,
                    help="run the JAX profiler over the serving session, "
                         "tracing armed, and write its trace under this "
                         "directory: the served spans and the device's "
                         "operations on one clock (TensorBoard, or "
                         "perfetto_trace.json.gz in Perfetto)")
    args = ap.parse_args()
    args.backend = _pick_backend(args)

    telemetry = args.metrics_dump or args.profile_dir
    if telemetry and (args.async_serve or args.graph_app or args.llm):
        with _telemetry(args):
            if args.async_serve:
                _serve_async(args)
            elif args.llm:
                _serve_llm(args)
            else:
                _serve_graph_app(args)
        return
    if args.async_serve:
        _serve_async(args)
        return
    if args.llm:
        _serve_llm(args)
        return
    if args.graph_app:
        _serve_graph_app(args)
        return

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encdec:
        raise SystemExit("whisper-family serving demo: see examples/serve_pruned_lm.py")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = Engine(model, params, batch_size=args.batch, max_len=args.max_len)

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)

    t0 = time.time()
    result = engine.generate(prompts, args.new_tokens)
    dt = time.time() - t0
    print(f"generated {result.tokens.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print("first row:", result.tokens[0].tolist())

    if args.scheduler:
        sched = RequestScheduler(engine)
        for rid in range(args.batch * 2):  # 2x oversubscribed queue
            plen = int(rng.integers(4, args.prompt_len))
            sched.submit(Request(rid=rid, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                                 max_new=int(rng.integers(3, args.new_tokens))))
        done = sched.run()
        print(f"scheduler: completed {sum(r.done for r in done)} requests "
              f"(continuous batching over {args.batch} slots)")


if __name__ == "__main__":
    main()
