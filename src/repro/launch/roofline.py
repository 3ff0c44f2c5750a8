"""Roofline analysis (deliverable g): three terms per (arch x shape) cell
from the dry-run JSONs, dominant bottleneck, MODEL_FLOPS ratio, and a
markdown table for EXPERIMENTS.md section Roofline.

  compute    = HLO_FLOPs_per_device / 197e12           (bf16 peak / chip)
  memory     = HLO_bytes_per_device / 819e9            (HBM bw / chip)
  collective = collective_bytes_per_device / 50e9      (ICI link bw)

Numerators use the probe-corrected counts (dryrun.py); the table is
single-pod (256 chips) per the assignment.  ``roofline_fraction`` =
ideal_compute_time / max(all three) -- how close the step is to the
compute roof if perfectly overlapped.

Usage: python -m repro.launch.roofline [--dir results/dryrun] [--md out.md]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Any, Dict, List, Optional

from .mesh import TARGET_KIND, peaks

HW = peaks(TARGET_KIND)

__all__ = ["analyze_record", "build_table", "main"]


def analyze_record(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if rec.get("status") != "run" or not rec.get("ok"):
        return None
    chips = rec["chips"]
    cost = rec.get("cost_corrected") or rec["cost"]
    coll = rec.get("collectives_corrected") or rec["collectives"]
    flops_dev = cost["flops"]
    bytes_dev = cost["bytes_accessed"]
    coll_dev = coll["total_bytes"]
    t_compute = flops_dev / HW.peak_flops
    t_memory = bytes_dev / HW.hbm_bw
    t_collective = coll_dev / HW.ici_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_collective}
    dominant = max(terms, key=terms.get)
    model_fl = rec["model_flops"]
    hlo_total = flops_dev * chips
    useful = model_fl / hlo_total if hlo_total else 0.0
    # ideal step time = max(model FLOPs at peak, every argument byte read
    # once at HBM bw) -- decode is *legitimately* memory-bound (weights + KV
    # must stream), so a compute-only ideal would be meaningless there.
    t_ideal_c = model_fl / (chips * HW.peak_flops)
    t_ideal_m = rec["memory"]["argument_bytes"] / HW.hbm_bw
    t_ideal = max(t_ideal_c, t_ideal_m)
    bound = max(terms.values())
    frac = t_ideal / bound if bound > 0 else 0.0
    return {
        **{k: rec[k] for k in ("arch", "shape", "mesh", "step", "chips")},
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "model_flops": model_fl,
        "hlo_flops_total": hlo_total,
        "useful_ratio": useful,
        "t_ideal_s": t_ideal,
        "roofline_fraction": frac,
        "fits_hbm": rec["memory"]["fits_hbm"],
        "live_gib": rec["memory"]["live_bytes"] / 2**30,
    }


_SUGGEST = {
    "compute": "cut HLO FLOPs: less remat recompute, fuse epilogues, or prune (BSR) the big GEMMs",
    "memory": "cut HBM traffic: fuse producers/consumers, bf16 intermediates, smaller logits dtype",
    "collective": "cut ICI bytes: reduce-scatter instead of all-reduce, bf16 grads, remat policy that saves TP-boundary activations, sequence parallelism",
}


def build_table(records: List[Dict[str, Any]]) -> str:
    rows = [
        "| arch | shape | step | compute s | memory s | collective s | dominant | useful (6ND/HLO) | roofline frac | live GiB | fits |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in records:
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['step']} | "
            f"{r['t_compute_s']:.4f} | {r['t_memory_s']:.4f} | {r['t_collective_s']:.4f} | "
            f"**{r['dominant']}** | {r['useful_ratio']:.2f} | {r['roofline_fraction']:.2f} | "
            f"{r['live_gib']:.1f} | {'y' if r['fits_hbm'] else 'N'} |"
        )
    return "\n".join(rows)


def main() -> None:
    ap = argparse.ArgumentParser()
    default_dir = os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "results", "dryrun"
    )
    ap.add_argument("--dir", default=default_dir)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--md", default=None)
    args = ap.parse_args()

    records = []
    skips = []
    for path in sorted(glob.glob(os.path.join(args.dir, f"*__{args.mesh}.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "run":
            skips.append(f"{rec['arch']} {rec['shape']}: {rec['status']}")
            continue
        a = analyze_record(rec)
        if a:
            records.append(a)
        else:
            skips.append(f"{rec['arch']} {rec['shape']}: FAILED {rec.get('error','')}")
    table = build_table(records)
    print(table)
    print("\nSkipped/failed cells:")
    for s in skips:
        print("  ", s)
    print("\nPer-cell dominant-term advice:")
    for r in records:
        print(f"  {r['arch']:22s} {r['shape']:12s} -> {r['dominant']}: {_SUGGEST[r['dominant']]}")
    if args.md:
        with open(args.md, "w") as f:
            f.write(table + "\n")


if __name__ == "__main__":
    main()
