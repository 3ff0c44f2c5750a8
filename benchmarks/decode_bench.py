"""Autoregressive-decode benchmark: the decoder lowering
(`repro/models/transformer_graph.py`), the paged KV-cache
(`repro/serving/kvcache.py`) and token-level continuous batching
(`AsyncPlanServer.submit_llm`).

What is recorded (``results/BENCH_decode.json``, ``_smoke`` variant in CI):

1. **parity** -- prefill-plan logits vs the plain jnp ``forward`` on the
   same params (the whole lowering + PassManager pipeline must be invisible
   in the outputs); gated at 1e-4 in every mode.
2. **greedy** -- full autoregressive greedy decode through the paged
   pipeline (prefill plan -> per-token decode plan over ``gather``-ed cache
   spans) vs a naive jnp forward loop: exact token match, gated.
3. **plans** -- plan-step counts for both phase graphs, unfused vs through
   ``fuse_epilogue`` (rope folds into the q/k projections, residual adds
   into w_o/w_down, the final rmsnorm into the last w_down): the step
   reduction is gated (fused < unfused).
4. **serve** -- mixed-length prompts through ``AsyncPlanServer.submit_llm``
   continuous batching: decode tok/s, prefill/decode batch counts, and the
   zero-loss / zero-page-leak gates.  Wall-clock is recorded, never
   asserted, in interpret mode (it measures Python, not the schedule).

``--smoke`` shrinks traffic so CI exercises the full path without a TPU
(wired into ``make bench-smoke``).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import smoke_config
from repro.core.graph import compile_plan
from repro.core.graph.passes import optimize
from repro.kernels import ops as kops
from repro.models.transformer import forward, init_lm
from repro.models.transformer_graph import build_decoder_graph, decoder_cache_spec
from repro.serving import AsyncPlanServer, PagedKVCache
from repro.utils.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")

ARCH = "qwen2.5-3b"


def _greedy_naive(params, cfg, prompt, steps):
    seq = [int(t) for t in prompt]
    for _ in range(steps):
        logits, _ = forward(params, cfg, jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def bench_decode(smoke: bool = False, out_path: str | None = None) -> dict:
    interpret = kops.interpret_default()
    backend = "reference" if interpret else "kernel"
    record: dict = {
        "mode": "interpret" if interpret else "hw",
        "smoke": smoke,
        "backend": backend,
        "arch": ARCH,
        "parity": [],
        "greedy": {},
        "plans": [],
        "serve": {},
    }
    cfg = smoke_config(ARCH)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    # 3. plan-step reduction through the epilogue-fusion pipeline
    graphs, plans = {}, {}
    for phase in ("prefill", "decode"):
        g = build_decoder_graph(params, cfg, phase=phase)
        go = optimize(g)
        graphs[phase] = go
        plans[phase] = compile_plan(go, backend=backend, interpret=interpret)
        row = {
            "phase": phase,
            "steps_unfused": len(compile_plan(g, backend=backend,
                                              interpret=interpret).steps),
            "steps_fused": len(plans[phase].steps),
        }
        record["plans"].append(row)
        assert row["steps_fused"] < row["steps_unfused"], row
        print(f"decode_plan,{phase},steps={row['steps_fused']}"
              f"(unfused={row['steps_unfused']})")

    # 1. prefill parity vs the plain jnp forward -- gates in every mode
    b, s = (2, 12) if smoke else (4, 24)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    lens = jnp.full((b,), s, jnp.int32)
    want, _ = forward(params, cfg, tok)
    go = graphs["prefill"]
    outs = plans["prefill"](go.params, tok, pos, lens)
    err = float(jnp.max(jnp.abs(
        outs[0][..., : cfg.vocab] - want[..., : cfg.vocab]
    )))
    assert err <= 1e-4, err
    record["parity"].append(
        {"case": f"prefill:{backend}", "max_err": err, "tokens": b * s}
    )
    print(f"decode_parity,prefill:{backend},{err:.2e}")

    # 2. greedy decode through the paged pipeline vs the naive jnp loop
    spec = decoder_cache_spec(cfg)
    g_, dh = spec["n_kv_heads"], spec["head_dim"]
    n_new = 4 if smoke else 8
    prompt = [int(t) for t in rng.integers(0, cfg.vocab, size=5)]
    want_toks = _greedy_naive(params, cfg, prompt, n_new)
    cache = PagedKVCache(num_pages=16, page_size=4, **spec)
    cache.allocate(0)
    tok1 = jnp.asarray([prompt], jnp.int32)
    pos1 = jnp.asarray([list(range(len(prompt)))], jnp.int32)
    len1 = jnp.asarray([len(prompt)], jnp.int32)
    outs = plans["prefill"](graphs["prefill"].params, tok1, pos1, len1)
    kvs = [np.asarray(o[0]).reshape(len(prompt), g_, dh) for o in outs[1:]]
    cache.append(0, np.stack(kvs[0::2], 1), np.stack(kvs[1::2], 1))
    got = [int(np.argmax(np.asarray(outs[0])[0, -1]))]
    for _ in range(n_new - 1):
        n = cache.length(0)
        cache.ensure_capacity(0, n + 1)
        k_ctx, v_ctx, lens_d = cache.gather([0], min_tokens=n + 1)
        outs = plans["decode"](
            graphs["decode"].params, jnp.asarray([[got[-1]]], jnp.int32),
            jnp.asarray([[n]], jnp.int32), jnp.asarray(k_ctx),
            jnp.asarray(v_ctx), jnp.asarray(lens_d),
        )
        kvs = [np.asarray(o[0]).reshape(1, g_, dh) for o in outs[1:]]
        cache.append(0, np.stack(kvs[0::2], 1), np.stack(kvs[1::2], 1))
        got.append(int(np.argmax(np.asarray(outs[0])[0, -1])))
    cache.release(0)
    cache.check_invariants()
    match = got == want_toks
    record["greedy"] = {
        "backend": backend, "tokens": n_new, "match": match,
        "plan": got, "naive": want_toks,
    }
    assert match, (got, want_toks)
    print(f"decode_greedy,{backend},{n_new}tokens,match={match}")

    # 4. continuous batching through the server: mixed prompt lengths,
    # zero sequence loss, zero page leak
    n_seq = 4 if smoke else 12
    new_tokens = 4 if smoke else 8
    prompts = [
        rng.integers(0, cfg.vocab, size=int(rng.integers(3, 10))).astype(np.int32)
        for _ in range(n_seq)
    ]
    cache = PagedKVCache(num_pages=32, page_size=4, **spec)
    server = AsyncPlanServer()
    server.add_llm("lm", prefill=plans["prefill"], decode=plans["decode"],
                   cache=cache, max_batch=3)
    t0 = time.perf_counter()
    handles = [
        server.submit_llm("lm", p, max_new_tokens=new_tokens) for p in prompts
    ]
    while any(not h.done() for h in handles):
        server.step()
    dt = time.perf_counter() - t0
    lost = sum(1 for h in handles if h.exception() is not None)
    st = server.stats["per_llm"]["lm"]
    server.close()
    cache.check_invariants()
    toks = sum(len(h.result(0)) for h in handles if h.exception() is None)
    record["serve"] = {
        "sequences": n_seq, "new_tokens": new_tokens, "lost": lost,
        "generated_tokens": toks, "wall_s": dt, "tok_per_s": toks / dt,
        "prefill_batches": st["prefill_batches"],
        "decode_batches": st["decode_batches"],
        "decode_tokens": st["decode_tokens"],
        "leaked_pages": cache.used_pages,
        "peak_pages": cache.stats["peak_used"],
    }
    assert lost == 0 and cache.used_pages == 0, record["serve"]
    print(f"decode_serve,{n_seq}seq,{toks}tok,{toks / dt:.1f}tok/s,"
          f"prefill={st['prefill_batches']},decode={st['decode_batches']},"
          f"lost={lost},leaked={cache.used_pages}")

    default_name = "BENCH_decode_smoke.json" if smoke else "BENCH_decode.json"
    out_path = out_path or os.path.join(RESULTS_DIR, default_name)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"decode,saved,{os.path.abspath(out_path)}")
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny traffic (CI, no TPU)")
    bench_decode(smoke=ap.parse_args().smoke)
