"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell against the production mesh using
ShapeDtypeStruct inputs -- no allocation, real SPMD partitioning.

Lowering strategy (DESIGN.md section 8):

* the FULL model compiles in scan-mode (repeated layer pattern as one
  ``lax.scan``): proves sharding coherence and gives the realistic
  per-device memory picture (while-loop bodies reuse buffers);
* XLA's cost analysis counts a while body ONCE, so HLO FLOPs / bytes /
  collective bytes are reconstructed exactly from two small *unrolled
  probes* (1 and 2 pattern-units): ``total = f(1) + (units-1) * (f(2)-f(1))``
  -- per-layer deltas include real fusion effects.  The probe pair and the
  extrapolation are recorded per cell.

Per cell -> results/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis(), corrected cost, per-kind collective bytes, analytic
MODEL_FLOPS, parameter counts; consumed by launch/roofline.py.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--force]
"""

import argparse
import dataclasses
import gc
import json
import os
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, SHAPES, get_config, shape_cells
from ..models import get_model
from ..models import transformer as lm
from ..models.sharding import FSDP_RULES, batch_spec, param_pspecs
from ..training.optimizer import AdamWConfig, AdamWState, adamw_init, adamw_update, zero1_pspecs
from ..utils.flops import model_flops, param_counts
from ..utils.hlo import collective_bytes
from .mesh import TARGET_KIND, make_production_mesh, peaks

HW = peaks(TARGET_KIND)

RESULTS_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "results", "dryrun"
)


# --------------------------------------------------------------------------- #
# sharding for inputs & caches                                                 #
# --------------------------------------------------------------------------- #


def _cache_pspecs(cache_tree: Any, bspec: P) -> Any:
    """Decode-cache shardings: batch over the data axes, the long axis
    (sequence / heads) over ``model`` -- flash-decoding-style split-K."""
    batch_axes = bspec[0] if len(bspec) else None

    def spec(path, leaf):
        name = jax.tree_util.keystr(path)
        nd = len(leaf.shape)
        if nd <= 1:
            return P(batch_axes) if nd == 1 else P()
        if name.endswith("['conv']"):  # [B, w-1, C]
            return P(batch_axes, None, "model")
        if nd >= 3:  # k/v/c_kv/k_rope/state: [B, S|H, ...]
            return P(batch_axes, "model", *([None] * (nd - 2)))
        return P(batch_axes, "model")  # rec h: [B, W]

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    return jax.tree_util.tree_unflatten(treedef, [spec(p, l) for p, l in flat])


def _batch_pspecs(batch_tree: Any, bspec: P) -> Any:
    return jax.tree.map(
        lambda leaf: P(
            bspec[0] if len(bspec) else None, *([None] * (len(leaf.shape) - 1))
        ),
        batch_tree,
    )


# --------------------------------------------------------------------------- #
# lower+compile one configuration                                              #
# --------------------------------------------------------------------------- #


def _data_parallel_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def _maybe_replicate_batch(specs, tree, mesh):
    """Drop any spec axis whose mesh extent does not divide the dim
    (long_500k has global_batch=1 -> TP-only decode; whisper's cross-KV has
    T_enc=1500 which 16 does not divide -> replicated sequence)."""

    def axis_size(entry) -> int:
        n = 1
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            if a is not None:
                n *= mesh.shape[a]
        return n

    def fix(leaf, spec):
        if not len(spec):
            return spec
        parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
        out = []
        for dim, entry in enumerate(parts):
            if entry is not None and leaf.shape[dim] % axis_size(entry) != 0:
                out.append(None)
            else:
                out.append(entry)
        return P(*out)

    return jax.tree.map(fix, tree, specs)


def _build(cfg, shape_name: str, mesh, *, zero1: bool, remat: bool, scan: bool,
           overrides=None):
    """Returns (fn, args, in_shardings, step_name).

    ``overrides`` (perf-iteration hooks, benchmarks/perf_iterations.py):
      rules: 'default'|'fsdp'|explicit rules list
      residual_spec: PartitionSpec constraint on the residual stream
      remat_policy: 'full'|'dots'
    """
    overrides = overrides or {}
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # FSDP rules whenever TP-only weight shards would exceed ~1/4 of HBM
    from ..utils.flops import param_counts as _pc

    per_chip_tp = _pc(cfg, params_shapes)["total"] * 2 / mesh.shape["model"]
    rules = FSDP_RULES if per_chip_tp > HW.hbm_bytes / 4 else None
    ro = overrides.get("rules")
    if ro is not None:
        if isinstance(ro, str):
            rules = {"default": None, "fsdp": FSDP_RULES}[ro]
        else:
            rules = ro
    p_specs = param_pspecs(params_shapes, rules)
    bspec = batch_spec(mesh)
    step_name, batch_specs, cache_specs = model.input_specs(shape)

    def shard(specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    if step_name == "train_step":
        opt_cfg = AdamWConfig()
        opt_shapes = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_shapes)
        mv = (zero1_pspecs(p_specs, params_shapes, data_size=mesh.shape["data"])
              if zero1 else p_specs)
        opt_specs = AdamWState(step=P(), m=mv, v=mv)

        if cfg.is_encdec:
            from ..models import encdec as encdec_mod

            def loss(p, b):
                return encdec_mod.loss_fn(p, cfg, b, remat=remat, layout_scan=scan)
        else:
            def loss(p, b):
                return lm.loss_fn(
                    p, cfg, b, remat=remat, layout_scan=scan,
                    remat_policy=overrides.get("remat_policy", "full"),
                    residual_spec=overrides.get("residual_spec"),
                )

        def fn(params, opt, batch):
            # allow_int: packed sparse params carry int32 indices (kept /
            # block_rows); their float0 cotangents are skipped by adamw_update
            (l, _), grads = jax.value_and_grad(loss, has_aux=True, allow_int=True)(
                params, batch
            )
            new_params, new_opt, _ = adamw_update(grads, opt, params, opt_cfg)
            return new_params, new_opt, l

        b_specs = _maybe_replicate_batch(
            _batch_pspecs(batch_specs, bspec), batch_specs, mesh
        )
        return (
            fn,
            (params_shapes, opt_shapes, batch_specs),
            (shard(p_specs), shard(opt_specs), shard(b_specs)),
            step_name,
        )
    if step_name == "prefill":
        if cfg.is_encdec:
            fn = lambda p, b: model.forward(p, b)
        else:
            def fn(p, b):
                return lm.forward(
                    p, cfg, b["tokens"], patch_embeds=b.get("patch_embeds"),
                    layout_scan=scan,
                    residual_spec=overrides.get("residual_spec"),
                    attn_chunk=overrides.get("attn_chunk", 1024),
                )[0]
        b_specs = _maybe_replicate_batch(
            _batch_pspecs(batch_specs, bspec), batch_specs, mesh
        )
        return (
            fn,
            (params_shapes, batch_specs),
            (shard(p_specs), shard(b_specs)),
            step_name,
        )
    # serve_step (decode): layer loop is cheap to compile; always unrolled
    def fn(p, b, caches):
        return model.decode_step(p, b, caches)

    b_specs = _maybe_replicate_batch(
        _batch_pspecs(batch_specs, bspec), batch_specs, mesh
    )
    c_specs = _maybe_replicate_batch(
        _cache_pspecs(cache_specs, bspec), cache_specs, mesh
    )
    return (
        fn,
        (params_shapes, batch_specs, cache_specs),
        (shard(p_specs), shard(b_specs), shard(c_specs)),
        step_name,
    )


def _compile_once(cfg, shape_name, mesh, *, zero1, remat, scan, overrides=None):
    fn, args, in_sh, step_name = _build(
        cfg, shape_name, mesh, zero1=zero1, remat=remat, scan=scan,
        overrides=overrides,
    )
    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
    t2 = time.time()
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    total, per_kind = collective_bytes(compiled.as_text())
    out = {
        "step": step_name,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        },
        "cost": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": {"total_bytes": int(total), "per_kind": per_kind},
    }
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    out["memory"]["live_bytes"] = int(live)
    out["memory"]["fits_hbm"] = bool(live < HW.hbm_bytes)
    del compiled, lowered
    gc.collect()
    return out


def _probe_cfg(cfg, n_units: int):
    """Config with prefix + n_units pattern-units of layers (unrolled probes)."""
    prefix, unit, _, _ = lm.scan_plan(cfg)
    n_layers = len(prefix) + n_units * unit
    kw = {"n_layers": n_layers}
    if cfg.is_encdec:
        kw["encoder_layers"] = n_units  # probe enc+dec pairs together
    return dataclasses.replace(cfg, **kw), unit, len(prefix)


# --------------------------------------------------------------------------- #
# one cell                                                                     #
# --------------------------------------------------------------------------- #


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    *,
    zero1: bool = True,
    remat: bool = True,
    probes: bool = True,
    verbose: bool = True,
    overrides: Optional[Dict[str, Any]] = None,
    cfg_override=None,
) -> Dict[str, Any]:
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    status = shape_cells(arch)[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "status": status,
    }
    if status != "run":
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec["chips"] = mesh.size
    model = get_model(cfg)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    counts = param_counts(cfg, params_shapes)
    shape = SHAPES[shape_name]
    rec.update(
        params_total=counts["total"],
        params_active=counts["active"],
        model_flops=model_flops(cfg, shape, counts),
    )

    try:
        # 1) full model, scan-mode: compile proof + memory picture
        full = _compile_once(
            cfg, shape_name, mesh, zero1=zero1, remat=remat, scan=True,
            overrides=overrides,
        )
        rec.update(full)
        rec["ok"] = True

        # 2) probes (unrolled): exact per-unit cost extrapolation
        if probes:
            prefix, unit, n_units, suffix = lm.scan_plan(cfg)
            if cfg.is_encdec:
                n_total_units, rem_layers = cfg.n_layers, 0
            else:
                n_total_units, rem_layers = n_units, len(suffix)
            cfg1, _, _ = _probe_cfg(cfg, 1)
            cfg2, _, _ = _probe_cfg(cfg, 2)
            p1 = _compile_once(cfg1, shape_name, mesh, zero1=zero1, remat=remat,
                               scan=False, overrides=overrides)
            p2 = _compile_once(cfg2, shape_name, mesh, zero1=zero1, remat=remat,
                               scan=False, overrides=overrides)

            def extra(field, sub=None):
                a = p1[field][sub] if sub else p1[field]
                b = p2[field][sub] if sub else p2[field]
                d = b - a
                scale = (n_total_units - 1) + rem_layers / unit
                return a + d * scale, d

            flops, flops_per_unit = extra("cost", "flops")
            bytes_, bytes_per_unit = extra("cost", "bytes_accessed")
            coll, coll_per_unit = extra("collectives", "total_bytes")
            per_kind = {}
            for k in set(p1["collectives"]["per_kind"]) | set(p2["collectives"]["per_kind"]):
                a = p1["collectives"]["per_kind"].get(k, 0)
                b = p2["collectives"]["per_kind"].get(k, 0)
                per_kind[k] = int(a + (b - a) * ((n_total_units - 1) + rem_layers / unit))
            rec["cost_corrected"] = {
                "flops": float(flops),
                "bytes_accessed": float(bytes_),
                "per_unit_flops": float(flops_per_unit),
                "probe_compile_s": [p1["compile_s"], p2["compile_s"]],
            }
            rec["collectives_corrected"] = {
                "total_bytes": float(coll),
                "per_kind": per_kind,
            }
    except Exception as e:  # noqa: BLE001 -- recorded, cell marked failed
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    gc.collect()
    if verbose:
        if rec.get("ok"):
            cc = rec.get("cost_corrected", rec.get("cost", {}))
            co = rec.get("collectives_corrected", rec.get("collectives", {}))
            print(
                f"[ok] {arch:22s} {shape_name:12s} {mesh_kind:6s} "
                f"compile={rec['compile_s']:6.1f}s flops/dev={cc.get('flops', 0):.3e} "
                f"coll/dev={co.get('total_bytes', 0):.3e}B "
                f"live={rec['memory']['live_bytes'] / 2**30:.2f}GiB",
                flush=True,
            )
        else:
            print(f"[FAIL] {arch} {shape_name} {mesh_kind}: {rec.get('error')}", flush=True)
    return rec


# --------------------------------------------------------------------------- #
# driver                                                                       #
# --------------------------------------------------------------------------- #


def cell_path(out_dir: str, arch: str, shape: str, mesh: str) -> str:
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh}.json")


def main() -> None:
    # 512 host devices for the production meshes: set before the first
    # backend init, never at import (importing this module must leave the
    # process's device state alone)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--seqpar", action="store_true",
                    help="sequence-parallel residual stream (the section-Perf winner)")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                path = cell_path(args.out, arch, shape, mesh_kind)
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        rec = json.load(f)
                    print(f"[cached] {arch} {shape} {mesh_kind} ok={rec.get('ok')}")
                else:
                    overrides = None
                    if args.seqpar:
                        overrides = {"residual_spec": P(
                            ("pod", "data") if mesh_kind == "multi" else "data",
                            "model", None)}
                    rec = run_cell(
                        arch, shape, mesh_kind,
                        zero1=not args.no_zero1, remat=not args.no_remat,
                        probes=(not args.no_probes) and mesh_kind == "single",
                        overrides=overrides,
                    )
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                if rec["status"] != "run":
                    n_skip += 1
                elif rec.get("ok"):
                    n_ok += 1
                else:
                    n_fail += 1
    print(f"\ndry-run matrix: ok={n_ok} fail={n_fail} skip={n_skip}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
