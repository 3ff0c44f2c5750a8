"""Readings the limits and the cells are set from, on the chip.

    python3 bench/calibrate.py seeds --workload <name> --seeds 1,2,3 --seconds 8 \
        [--control] [--out chiprun_out/calib.jsonl]
    python3 bench/calibrate.py sweep --workload <name> --streams 2,3,4,5,6 --seconds 8

``seeds`` runs the cell's whole timed path once per seed in one process
(set-up, a window at the cell's own load, the comparison with the plain
reference) and prints each run's compared numbers; with ``--control`` it
also puts the configuration's control (the reference at the precision its
``control`` names) in the program's place on the same sample and prints
the same numbers for it, and whether it came out correct; with ``--grid``
it reads every compared number between the served frames, or the
reference at each precision the grid names, and the reference at each
other one.

``sweep`` sets a frame cell up once and measures it at each stream count,
printing the latency percentiles and whether the backlog grew (the mean
latency of the window's last quarter against its first): the knee is the
largest count with no growing backlog.  The benchmark's own runs
(``run.py``) never run either.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _prepare() -> None:
    """As ``run.py``: paths, the compile cache's fixed place, no TPU logs."""
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def seeds(cell, args):
    from yardstick import runner

    for seed in [int(s) for s in args.seeds.split(",")]:
        rec = {"workload": cell.name, "seed": seed}

        def after(drv):
            if args.control:
                checks = drv.control()
                rec["control_correct"] = all(c.ok for c in checks)
                rec["control_checks"] = {c.name: c.value for c in checks}
            if args.grid:
                rec["grid"] = grid(drv, json.loads(args.grid))

        t = time.perf_counter()
        res = runner.run(cell, seed, args.seconds, False, t_start=t, after=after)
        rec.update(correct=res["correct"], checks=res["checks"], metrics=res["metrics"],
                   attempted=res["attempted"], failed=res["failed"],
                   memory_peak_bytes=res["device"]["memory_peak_bytes"])
        _emit(args.out, rec)
        gc.collect()  # the last seed's weights and plans leave the chip first


def grid(drv, precisions):
    """``frame_err`` and ``frame_rms_err`` (``compare.frame_numbers``)
    between the served frames or the reference at each named precision,
    and the reference at each other named precision."""
    from yardstick import compare

    sched, kept = drv.raw["sched"], drv.raw["kept"]
    refs = {name: drv.reference_frames(p) for name, p in precisions.items()}
    got = {"program": kept}
    got.update({name: {i: f[sched[i].item] for i in kept} for name, f in refs.items()})
    return {
        f"{a}~{b}": compare.frame_numbers([got[a][i] for i in kept], [want[sched[i].item] for i in kept])
        for a in got for b, want in refs.items() if a != b
    }


def sweep(cell, args):
    import numpy as np

    from yardstick import stats

    drv = cell.driver()(cell, int(args.seed))
    drv.setup()
    for n in [int(s) for s in args.streams.split(",")]:
        drv.tp = dict(drv.tp, streams=n)
        drv.measure(args.seconds)
        lat = 1e3 * drv.raw["latency_s"]
        q = max(1, len(lat) // 4)
        late = drv.raw["late_s"]
        st = drv.raw["stats"]
        _emit(args.out, {
            "workload": cell.name, "streams": n, "frames": len(lat), "failed": drv.failed,
            "p50_ms": stats.percentile(lat, 50), "p90_ms": stats.percentile(lat, 90),
            "p95_ms": stats.percentile(lat, 95), "p99_ms": stats.percentile(lat, 99),
            "max_ms": float(np.max(lat)),
            "first_quarter_mean_ms": float(np.mean(lat[:q])), "last_quarter_mean_ms": float(np.mean(lat[-q:])),
            "generator_late_p99_ms": 1e3 * stats.percentile(late[~np.isnan(late)], 99),
            "batches": st["batches"], "padded_frames": st["padded_frames"],
        })
    drv.release()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("seeds", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", default="1")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--grid", default="", help="JSON: {name: {storage, operands}} to read against each other")
    ap.add_argument("--streams", default="2,3,4,5,6")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    _prepare()

    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from yardstick import cells, device

    cell = cells.find(args.workload, ROOT)
    try:
        device.require(cell.chips)
    except device.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    (seeds if args.mode == "seeds" else sweep)(cell, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
