"""Bring-up smoke run on one TPU chip: the main serving paths at real sizes.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py

Phases, in one process, stopping at the first failure:

1. device   -- print the platform, device kind and count; refuse anything
               but a TPU, and refuse Pallas interpret mode.
2. frames   -- the paper's three apps at their builders' width (base 32,
               sparsity 0.5) through PassManager -> compile_plan(kernel) ->
               AsyncPlanServer at batch 4 on 512x512-output frames, plus one
               W8A8 coloring plan on the quant backend.  Every served frame
               (the chip's default precision: bf16 passes) is checked
               against the reference plan at highest precision, and so is
               the kernel plan run under highest, to f32 rounding.
3. decoder  -- qwen2.5-3b at its published widths, depth cut to 4 layers,
               lowered to prefill/decode plans and served over the paged KV
               cache; prefill logits (default and highest precision) are
               checked against the jnp model forward at highest precision,
               greedy tokens against the jnp model wherever the top-2 logit
               margin is wider than the default-precision tolerance.

The last line of stdout is the JSON result, printed only when every phase
passed.  The ms/frame and tok/s figures are smoke timings of one short run
(compilation excluded), not a benchmark.  Weights and inputs come from
``--seed``; nothing outside the checkout is read.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops as kops  # noqa: E402
from repro.launch import parity  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

#: frame gates, max |x - reference| / max |reference| per frame against the
#: reference plan at "highest" (see repro.launch.parity): the served frames,
#: at the chip's default matmul precision, within FRAME_RTOL; the kernel
#: plan run directly under "highest" within EXACT_RTOL
FRAME_RTOL = parity.FRAME_RTOL_TPU
EXACT_RTOL = parity.EXACT_RTOL
#: INT8 gate: max |quant plan - f32 reference| on the same frame, the bound
#: tests/test_quant.py holds the quant backend to
QUANT_ATOL = 5e-2
#: decoder gates, max |plan prefill logits - jnp forward logits at
#: "highest"| at the last prompt position: within LOGIT_ATOL at the chip's
#: default precision, within EXACT_LOGIT_ATOL under "highest".  Greedy
#: tokens must match wherever the reference top-2 margin exceeds LOGIT_ATOL.
LOGIT_ATOL = parity.LOGIT_ATOL_TPU
EXACT_LOGIT_ATOL = parity.EXACT_LOGIT_ATOL

APP_SIZES = {  # (input side, output side): super resolution upsamples 2x
    "style_transfer": (512, 512),
    "coloring": (512, 512),
    "super_resolution": (256, 512),
}


class SmokeFailure(RuntimeError):
    """A phase's gate failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# --------------------------------------------------------------------------- #
# device                                                                      #
# --------------------------------------------------------------------------- #


def phase_device() -> dict:
    d = jax.devices()[0]
    dev = {"platform": d.platform, "kind": d.device_kind, "count": jax.device_count()}
    print(f"device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}")
    _check(dev["platform"] == "tpu", f"no TPU: JAX runs on {dev['platform']!r}")
    _check(
        "REPRO_PALLAS_INTERPRET" not in os.environ,
        "REPRO_PALLAS_INTERPRET is set: a chip run never uses interpret mode",
    )
    _check(not kops.interpret_default(), "Pallas interpret mode is on")
    return dev


# --------------------------------------------------------------------------- #
# frames                                                                      #
# --------------------------------------------------------------------------- #


def _lowering_counts(plan) -> dict:
    """The current process counters, read right after a plan was traced."""
    from repro.core.graph.executor import jnp_route_counts

    convs = sum(s.node.op in ("conv2d", "qconv2d") for s in plan.steps)
    fast = sum(kops.conv_fastpath_counts().values())
    fallback = kops.conv_fallback_counts()
    return {
        "steps": len(plan.steps),
        "conv_steps": convs,
        "conv_pallas": convs - fast - sum(fallback.values()),
        "conv_gemm1x1": fast,
        "conv_lax": fallback,
        "jnp_route": jnp_route_counts(),
    }


def _reset_lowering_counts() -> None:
    from repro.obs import metrics

    kops.reset_conv_fallbacks()
    kops.reset_conv_fastpaths()
    metrics.registry().reset("kernel_jnp_route_total")


def _serve_frames(server, name, frames, batch):
    """Warm ``name``'s compiled chunk, then serve ``frames``; returns the
    outputs and the smoke timing (ms/frame, compilation excluded)."""
    for h in [server.submit(name, f) for f in frames[:batch]]:
        h.result()
    t0 = time.perf_counter()
    handles = [server.submit(name, f) for f in frames]
    outs, failed = [], 0
    for h in handles:
        try:
            outs.append(np.asarray(h.result()))
        except Exception as e:  # counted; the gate below names the first
            failed += 1
            print(f"frames: {name}: request {h.rid} failed: {e!r}", file=sys.stderr)
    ms = (time.perf_counter() - t0) * 1e3 / len(frames)
    _check(failed == 0, f"{name}: {failed} failed handles")
    return outs, ms


def _at_highest(plan, params, frames, batch):
    """``plan`` on ``frames`` at highest matmul precision (which reaches
    the Pallas kernels' contractions too)."""
    bp = plan.batched(batch)
    with jax.default_matmul_precision("highest"):
        return np.asarray(bp(params, jnp.stack(frames)))


def phase_frames(
    *, base=32, sizes=APP_SIZES, n_frames=16, batch=4, sparsity=0.5, seed=0,
    interpret=None, quant_app="coloring",
) -> dict:
    """Serve every app's kernel plan (and one INT8 plan) through one
    AsyncPlanServer and hold each served frame to the reference plan."""
    from repro.core.graph import PassContext, PassManager, compile_plan
    from repro.models.cnn import APP_ACT_SKIP, APP_QUANT_SKIP, APPS, app_masks
    from repro.quant import calibrate_plan
    from repro.serving import AsyncPlanServer

    rng = np.random.default_rng(seed)
    server = AsyncPlanServer(flush_after=0.005)
    report = {}
    with server:
        server.start()
        for app, (size, out_size) in sizes.items():
            g = APPS[app](jax.random.PRNGKey(seed), base=base)
            masks, structures = app_masks(g, app, sparsity=sparsity)
            go = PassManager().run(g, PassContext(masks=masks, structures=structures))
            plan = compile_plan(go, backend="kernel", interpret=interpret)
            plan_ref = compile_plan(go, backend="reference")
            c_in = 1 if app == "coloring" else 3
            shape = (c_in, size, size)
            server.add_plan(app, plan, go.params, batch, input_spec=[(shape, jnp.float32)])
            frames = [
                jnp.asarray(rng.standard_normal(shape), jnp.float32)
                for _ in range(n_frames)
            ]
            _reset_lowering_counts()
            outs, ms = _serve_frames(server, app, frames, batch)
            counts = _lowering_counts(plan)
            ref = _at_highest(plan_ref, go.params, frames, batch)
            _check(
                ref.shape[2:] == (out_size, out_size),
                f"{app}: output {ref.shape[2:]} is not {out_size}x{out_size}",
            )
            rel = max(parity.rel_err(o, r) for o, r in zip(outs, ref))
            exact = _at_highest(plan, go.params, frames, batch)
            rel_exact = max(parity.rel_err(o, r) for o, r in zip(exact, ref))
            rec = {**counts, "rel_err": rel, "rel_err_highest": rel_exact,
                   "ms_per_frame_smoke": ms, "peak_bytes": _peak_bytes()}
            report[app] = rec
            print(f"frames: {app}: {json.dumps(rec)}")
            _check(rel <= FRAME_RTOL, f"{app}: rel err {rel:.3g} > {FRAME_RTOL}")
            _check(
                rel_exact <= EXACT_RTOL,
                f"{app}: rel err under highest {rel_exact:.3g} > {EXACT_RTOL}",
            )

            if app != quant_app:
                continue
            # W8A8 on the quant backend, calibrated on the f32 reference plan
            # as launch/serve.py --quantize does
            calib = [
                jnp.asarray(rng.standard_normal((batch, *shape)), jnp.float32)
                for _ in range(2)
            ]
            table = calibrate_plan(plan_ref, go.params, calib)
            gq = PassManager(("quantize",)).run(go, PassContext(
                calibration=table, quant_skip=APP_QUANT_SKIP[app],
                act_quant_skip=APP_ACT_SKIP[app],
            ))
            qname = f"{app}_int8"
            plan_q = compile_plan(gq, backend="quant", interpret=interpret)
            server.add_plan(qname, plan_q, gq.params, batch, input_spec=[(shape, jnp.float32)])
            _reset_lowering_counts()
            outs_q, ms_q = _serve_frames(server, qname, frames, batch)
            err = max(float(np.max(np.abs(o - r))) for o, r in zip(outs_q, ref))
            rec = {**_lowering_counts(plan_q), "abs_err_vs_f32": err,
                   "ms_per_frame_smoke": ms_q, "peak_bytes": _peak_bytes()}
            report[qname] = rec
            print(f"frames: {qname}: {json.dumps(rec)}")
            _check(err <= QUANT_ATOL, f"{qname}: abs err {err:.3g} > {QUANT_ATOL}")
        health = server.health()
        stats = server.stats
    print(f"frames: served={stats['completed']} failed=0 "
          f"tick_errors={health['tick_errors']} "
          f"tune_rejected={kops.tune_rejected_counts()}")
    _check(health["tick_errors"] == 0, f"{health['tick_errors']} tick errors")
    report["tune_rejected"] = kops.tune_rejected_counts()
    return report


# --------------------------------------------------------------------------- #
# decoder                                                                     #
# --------------------------------------------------------------------------- #


def decoder_config(n_layers=4):
    """qwen2.5-3b at its published widths, depth cut to ``n_layers``, f32
    (the plan path is f32-only)."""
    from repro.configs import get_config

    full = get_config("qwen2.5-3b")
    print(f"decoder: depth cut to {n_layers} of {full.n_layers} layers: f32 "
          f"weights for all of them with the untied {full.vocab}-row embedding "
          f"and head come to ~13.7 GB of the chip's 16 GB")
    return dataclasses.replace(full, n_layers=n_layers, dtype="float32")


def phase_decoder(
    cfg=None, *, n_seqs=4, prompt_range=(16, 64), new_tokens=8, page_size=16,
    seed=0, interpret=None,
) -> dict:
    """Serve ``cfg`` through prefill/decode plans over the paged KV cache
    and hold it to the jnp model."""
    from repro.core.graph import compile_plan
    from repro.core.graph.passes import optimize
    from repro.models.transformer import init_lm
    from repro.models.transformer_graph import build_decoder_graph, decoder_cache_spec
    from repro.serving import AsyncPlanServer, PagedKVCache

    cfg = cfg or decoder_config()
    params = init_lm(jax.random.PRNGKey(seed), cfg)
    plans = {
        phase: compile_plan(
            optimize(build_decoder_graph(params, cfg, phase=phase)),
            backend="kernel", interpret=interpret,
        )
        for phase in ("prefill", "decode")
    }
    rng = np.random.default_rng(seed)
    lo, hi = prompt_range
    prompts = [
        rng.integers(0, cfg.vocab, size=int(rng.integers(lo, hi + 1))).astype(np.int32)
        for _ in range(n_seqs)
    ]
    longest = max(len(p) for p in prompts) + new_tokens
    cache = PagedKVCache(
        num_pages=n_seqs * -(-longest // page_size) + 1, page_size=page_size,
        **decoder_cache_spec(cfg),
    )
    print(f"decoder: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"layers={cfg.n_layers} dtype={cfg.dtype} "
          f"prefill_steps={len(plans['prefill'].steps)} "
          f"decode_steps={len(plans['decode'].steps)} "
          f"prompts={[len(p) for p in prompts]}")

    # gate 1: the prefill plan's last-position logits vs the jnp forward
    s = max(len(p) for p in prompts)
    toks = np.zeros((n_seqs, s), np.int32)
    for j, p in enumerate(prompts):
        toks[j, : len(p)] = p
    lens = np.array([len(p) for p in prompts], np.int32)
    pos = np.broadcast_to(np.arange(s, dtype=np.int32), toks.shape)
    def prefill_last_logits():
        outs = plans["prefill"](plans["prefill"].graph.params, jnp.asarray(toks),
                                jnp.asarray(pos), jnp.asarray(lens))
        return np.asarray(outs[0])[np.arange(n_seqs), lens - 1, : cfg.vocab]

    want = parity.ref_next_logits(params, cfg, prompts, -(-longest // 8) * 8)
    logit_err = float(np.max(np.abs(prefill_last_logits() - want)))
    with jax.default_matmul_precision("highest"):
        logit_err_exact = float(np.max(np.abs(prefill_last_logits() - want)))

    # serve: token-level continuous batching over the paged cache
    server = AsyncPlanServer()
    server.add_llm("lm", prefill=plans["prefill"], decode=plans["decode"],
                   cache=cache, max_batch=n_seqs)
    with server:
        server.start()
        t0 = time.perf_counter()
        handles = [server.submit_llm("lm", p, max_new_tokens=new_tokens) for p in prompts]
        served = [[int(t) for t in h.result()] for h in handles]
        dt = time.perf_counter() - t0
        health = server.health()
    st = server.stats["per_llm"]["lm"]
    leaked = cache.occupancy()["used_pages"]
    cache.check_invariants()

    # gate 2: greedy tokens vs the jnp model, teacher-forced on the served
    # tokens, at highest precision
    agree = parity.greedy_agreement(params, cfg, prompts, served, LOGIT_ATOL)
    toks_out = sum(len(s) for s in served)
    rec = {
        "prefill_logit_err": logit_err, "prefill_logit_err_highest": logit_err_exact,
        "logit_peak": float(np.max(np.abs(want))), "greedy_match": agree["match"],
        "greedy_total": agree["total"], "near_ties": agree["near_ties"],
        "failed": st["failed"], "leaked": leaked, "tick_errors": health["tick_errors"],
        "tok_per_s_smoke": toks_out / dt, "peak_bytes": _peak_bytes(),
    }
    print(f"decoder: {json.dumps(rec)}")
    print(f"decoder: greedy tokens "
          f"{'match' if agree['match'] == agree['total'] else 'differ'}: "
          f"{agree['match']}/{agree['total']} equal, {agree['near_ties']} differ "
          f"inside a top-2 margin <= {LOGIT_ATOL}")
    _check(logit_err <= LOGIT_ATOL, f"prefill logit err {logit_err:.3g} > {LOGIT_ATOL}")
    _check(
        logit_err_exact <= EXACT_LOGIT_ATOL,
        f"prefill logit err under highest {logit_err_exact:.3g} > {EXACT_LOGIT_ATOL}",
    )
    _check(st["failed"] == 0, f"{st['failed']} failed sequences")
    _check(leaked == 0, f"{leaked} pages leaked")
    _check(health["tick_errors"] == 0, f"{health['tick_errors']} tick errors")
    _check(
        agree["worst_miss"] == 0.0,
        f"greedy token differs where the top-2 margin is {agree['worst_miss']:.3g}",
    )
    return rec


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        dev = phase_device()
        phase_frames(seed=args.seed, interpret=False)
        phase_decoder(seed=args.seed, interpret=False)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
