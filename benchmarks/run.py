"""Benchmark harness (deliverable d): one section per paper table/figure,
plus the roofline summary from the dry-run artifacts.

  table1   -> benchmarks/table1_apps.py   (paper Table 1, 3 apps x 3 variants)
  kernels  -> benchmarks/kernel_bench.py  (sparse-execution + storage tables)
  fusion   -> benchmarks/kernel_bench.py::bench_fusion
              (fused-elementwise kernel + fuse_epilogue plans; writes
              results/BENCH_fusion.json)
  admm     -> benchmarks/admm_bench.py    (pruning convergence/quality)
  roofline -> results/dryrun summary      (EXPERIMENTS.md section Roofline)

Output: CSV-ish lines ``name,...`` per table.
"""

from __future__ import annotations

import glob
import json
import os
import sys

from repro.utils.compile_cache import enable_compile_cache


def _roofline_summary() -> None:
    base = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    files = sorted(glob.glob(os.path.join(base, "*__single.json")))
    if not files:
        print("roofline,SKIP(no dry-run artifacts; run python -m repro.launch.dryrun --all)")
        return
    from repro.launch.roofline import analyze_record

    print("roofline,arch,shape,dominant,t_compute_s,t_memory_s,t_collective_s,useful,frac")
    for path in files:
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "run":
            continue
        a = analyze_record(rec)
        if a is None:
            print(f"roofline,{rec['arch']},{rec['shape']},FAILED,,,,,")
            continue
        print(
            f"roofline,{a['arch']},{a['shape']},{a['dominant']},"
            f"{a['t_compute_s']:.5f},{a['t_memory_s']:.5f},{a['t_collective_s']:.5f},"
            f"{a['useful_ratio']:.2f},{a['roofline_fraction']:.2f}"
        )


def main() -> None:
    enable_compile_cache()
    sections = sys.argv[1:] or ["table1", "kernels", "fusion", "admm", "roofline"]
    if "table1" in sections:
        from . import table1_apps

        table1_apps.main()
    if "kernels" in sections:
        from . import kernel_bench

        kernel_bench.main()  # includes the fusion section + BENCH_fusion.json
    elif "fusion" in sections:
        from . import kernel_bench

        kernel_bench.bench_fusion()
    if "admm" in sections:
        from . import admm_bench

        admm_bench.main()
    if "roofline" in sections:
        _roofline_summary()


if __name__ == "__main__":
    main()
