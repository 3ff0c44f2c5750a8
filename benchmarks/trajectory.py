"""Cross-PR bench regression trajectory.

``results/BENCH_*.json`` snapshots are one-shot: each bench overwrites its
file, so a perf regression only shows up if someone diffs the JSON by hand.
This module merges every committed snapshot into a single, *accumulating*
``results/BENCH_trajectory.json`` keyed by ``PR -> bench -> case``, and
asserts **floors** over the merged trajectory:

* **parity always** -- every case with a ``max_err`` is gated in every mode
  (f32 cases at 1e-4, int8 schemes at the repo-wide 5e-2 contract);
* **interpret-mode ratio floors** for the known-slow cases -- interpret-mode
  wall-clock measures the Python interpreter, not silicon, so speedups are
  *not* asserted > 1 there; instead each case carries a floor pinned just
  under its measured ratio so a regression (e.g. a kernel suddenly running
  4x more grid steps) still fails CI.  A ``note`` on the floor documents
  why the case is slow when it is;
* **hw-only speedup gates** -- any kernel case recorded from a real-TPU run
  (``mode == "hw"``) must beat its baseline outright (> 1.0).

Usage::

  python -m benchmarks.trajectory --merge --pr 6   # after a full bench run
  python -m benchmarks.trajectory --check          # CI / make bench-smoke

``--merge`` reads the full-mode ``BENCH_*.json`` files (smoke files are CI
plumbing, except the committed serving parity reference), updates the PR's
entry in the trajectory file, then runs the checker.  ``--check`` loads the
committed trajectory and asserts every floor on every recorded PR -- this is
the step wired into ``make bench-smoke`` and CI, so a floor regression fails
the smoke job even though CI never runs the full benches.
"""

from __future__ import annotations

import argparse
import fnmatch
import glob
import json
import os

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")
TRAJECTORY = "BENCH_trajectory.json"

# --------------------------------------------------------------------------- #
# floors                                                                       #
# --------------------------------------------------------------------------- #
#
# Keyed ``(bench, case-pattern)`` (fnmatch).  Fields:
#   max_err        parity ceiling, asserted in every mode
#   min_ratio      interpret-mode speedup floor (hw runs use the > 1.0 gate
#                  instead); pinned just under the measured ratio
#   max_steps      plan-step ceiling (fusion acceptance)
#   zero_fallbacks every conv lowered through the Pallas kernel
#   min_ratio_note documentation for why a floor sits below 1.0

FLOORS: dict = {
    # conv kernel interpret ratios compare a fixed ~1ms-per-grid-step Python
    # dispatch floor against an XLA-CPU baseline that scales with host CPU
    # speed, so they are machine-dependent: the PR-4 container measured
    # dense+f32 at 0.96x where this one measures ~0.5x on identical code.
    # Floors sit below the slowest host observed; the real perf contract is
    # the hw-mode gate (speedup > 1.0), asserted whenever mode != interpret.
    ("conv", "kernel:dense+f32:*"): {"max_err": 1e-4, "min_ratio": 0.25},
    ("conv", "kernel:chanprune+f32:*"): {"max_err": 1e-4, "min_ratio": 0.3},
    ("conv", "kernel:dense+w8:*"): {"max_err": 1.5e-1, "min_ratio": 0.25},
    ("conv", "kernel:dense+w8a8:*"): {
        "max_err": 1.5e-1,
        "min_ratio": 0.06,
        "min_ratio_note": (
            "w8a8 interpret ratio is an XLA-CPU artifact, not a kernel "
            "property: the baseline lax.conv runs XLA's fast f32 path while "
            "the interpreted kernel's int8xint8->int32 jnp.dot lowers to "
            "XLA-CPU's slow integer GEMM (~4x the f32 GEMM on the same "
            "shape).  On TPU the int8 MXU path is the fast one (hw gate "
            "asserts > 1.0).  Re-measured for PR 6 after tiled-K landed: "
            "the int8-GEMM artifact is unchanged; the headline ratio moved "
            "0.25x -> ~0.1x only because the faster PR-6 host shrank the "
            "lax baseline ~4.6x while the interpreter's Python floor stayed "
            "put (see the machine-dependence note above)."
        ),
    },
    ("conv", "app:*"): {"max_err": 1e-4, "zero_fallbacks": True},
    ("fusion", "elementwise:app_nchw"): {
        "max_err": 1e-4,
        "min_ratio": 0.6,
        "min_ratio_note": (
            "interpret-mode grid steps cost ~1ms of Python each; PR 6 "
            "re-seeded the interpret default block_m to the full padded M "
            "(one grid step), lifting this case from 0.13x to ~0.9x.  The "
            "remaining gap vs the unfused jnp chain is interpreter "
            "dispatch, not data movement (hw gate asserts > 1.0)."
        ),
    },
    ("fusion", "elementwise:lm_residual"): {"max_err": 1e-4, "min_ratio": 0.7},
    ("fusion", "plan:style_transfer"): {"max_err": 1e-4, "max_steps": 33},
    ("fusion", "plan:coloring"): {"max_err": 1e-4, "max_steps": 30},
    ("fusion", "plan:super_resolution"): {"max_err": 1e-4, "max_steps": 37},
    ("quant", "kernel:w8"): {"max_err": 5e-2, "min_ratio": 1.2},
    ("quant", "kernel:w8a8"): {
        "max_err": 5e-2,
        "min_ratio": 0.5,
        "min_ratio_note": (
            "same XLA-CPU integer-GEMM artifact as conv w8a8; the int8 "
            "weight stream is still 4x smaller (bytes_ratio gates in "
            "BENCH_quant.json) and the hw gate asserts > 1.0 on TPU."
        ),
    },
    ("quant", "app:*"): {"max_err": 5e-2},
    ("serving", "parity:*"): {"max_err": 1e-4},
    ("serving_smoke", "parity:*"): {"max_err": 1e-4},
    # multi-tenant overload gates (full + committed smoke reference): at 2x
    # capacity with a 10:1 hot/light skew, the in-quota light tenant loses
    # nothing and stays within its deadline SLO, the hot tenant's excess is
    # absorbed by quota + ladder transitions (require_ladder), and the armed
    # watchdog never fires (the overload response is policy, not a hang).
    ("serving", "multi_tenant"): {
        "zero_lost": True, "max_light_miss_rate": 0.1,
        "require_ladder": True, "zero_watchdog": True,
    },
    ("serving_smoke", "multi_tenant"): {
        "zero_lost": True, "max_light_miss_rate": 0.1,
        "require_ladder": True, "zero_watchdog": True,
    },
    # robustness gates (full + committed smoke reference): degraded-mode
    # overhead is guarded-under-total-failure vs the eager reference plan --
    # both are Python-dispatch bound, so the ratio is machine-stable (~1.0x
    # measured); 3.0x is the "guard rails must stay cheap" ceiling.  The
    # chaos cases gate semantics, not speed: zero lost requests, a surviving
    # scheduler thread, bit-exact total-demotion output, breaker recovery.
    ("robustness", "degraded:*"): {"max_err": 1e-4, "max_overhead": 3.0},
    ("robustness_smoke", "degraded:*"): {"max_err": 1e-4, "max_overhead": 3.0},
    ("robustness", "chaos"): {
        "max_err": 1e-4, "zero_lost": True, "require_survival": True,
    },
    ("robustness_smoke", "chaos"): {
        "max_err": 1e-4, "zero_lost": True, "require_survival": True,
    },
    ("robustness", "chaos_total"): {
        "zero_lost": True, "require_survival": True, "require_bitexact": True,
    },
    ("robustness_smoke", "chaos_total"): {
        "zero_lost": True, "require_survival": True, "require_bitexact": True,
    },
    ("robustness", "recovery"): {"require_recovered": True},
    ("robustness_smoke", "recovery"): {"require_recovered": True},
    # autoregressive-decode gates (full + committed smoke reference): the
    # decoder lowering must be invisible in the logits (parity), greedy
    # decode through the paged KV pipeline must match the naive jnp loop
    # token-for-token, epilogue fusion must actually shrink both phase
    # plans, and continuous-batching serve must lose zero sequences and
    # leak zero cache pages.
    ("decode", "parity:*"): {"max_err": 1e-4},
    ("decode_smoke", "parity:*"): {"max_err": 1e-4},
    ("decode", "greedy"): {"require_match": True},
    ("decode_smoke", "greedy"): {"require_match": True},
    ("decode", "plan:*"): {"require_fusion": True},
    ("decode_smoke", "plan:*"): {"require_fusion": True},
    ("decode", "serve"): {"zero_lost": True, "zero_leak": True},
    ("decode_smoke", "serve"): {"zero_lost": True, "zero_leak": True},
}


# --------------------------------------------------------------------------- #
# case extraction (one flat dict per bench snapshot)                           #
# --------------------------------------------------------------------------- #


def _cases_from(bench: str, rec: dict) -> dict:
    """Flatten a BENCH_<bench>.json record into ``{case_key: fields}``."""
    mode = rec.get("mode", "interpret")
    cases: dict = {}

    def put(key, **fields):
        cases[key] = {"mode": mode, **fields}

    if bench == "conv":
        for r in rec.get("kernels", ()):
            n, c, h, w, o = r["shape"]
            put(f"kernel:{r['scheme']}:{n}x{c}x{h}x{w}-{o}",
                speedup=r["speedup"], max_err=r["max_err"])
        for r in rec.get("apps", ()):
            put(f"app:{r['app']}", max_err=r["max_err"],
                plan_steps=r["plan_steps"], fallbacks=r["fallbacks"])
    elif bench == "fusion":
        for r in rec.get("elementwise", ()):
            put(f"elementwise:{r['case']}",
                speedup=r["speedup"], max_err=r["max_err"])
        for r in rec.get("epilogue_plans", ()):
            put(f"plan:{r['app']}", max_err=r["max_err"],
                plan_steps=r["steps_fused"], steps_unfused=r["steps_unfused"])
    elif bench == "quant":
        for r in rec.get("kernels", ()):
            put(f"kernel:{r['scheme']}",
                speedup=r["speedup"], max_err=r["max_err"])
        for r in rec.get("apps", ()):
            put(f"app:{r['app']}", max_err=r["max_err"],
                bytes_ratio=r["bytes_ratio"])
    elif bench.startswith("robustness"):
        for r in rec.get("degraded", ()):
            put(f"degraded:{r['app']}", max_err=r["max_err"],
                overhead=r["overhead"], clean_overhead=r.get("clean_overhead"))
        for key in ("chaos", "chaos_total"):
            c = rec.get(key)
            if c:
                put(key, max_err=c["max_err"], lost=c["lost_requests"],
                    injected=c["injected_faults"], bitexact=c["bitexact"],
                    survived=c["scheduler_survived"])
        rcv = rec.get("recovery")
        if rcv:
            put("recovery", recovered=rcv["recovered"],
                breaker_trips=rcv["breaker_trips"])
    elif bench.startswith("decode"):
        for r in rec.get("parity", ()):
            put(f"parity:{r['case']}", max_err=r["max_err"])
        g = rec.get("greedy")
        if g:
            put("greedy", match=g["match"], tokens=g["tokens"],
                backend=g["backend"])
        for r in rec.get("plans", ()):
            put(f"plan:{r['phase']}", plan_steps=r["steps_fused"],
                steps_unfused=r["steps_unfused"])
        srv = rec.get("serve")
        if srv:
            put("serve", lost=srv["lost"],
                leaked_pages=srv["leaked_pages"],
                tok_per_s=srv["tok_per_s"],
                decode_tokens=srv["decode_tokens"])
    elif bench.startswith("serving"):
        for r in rec.get("parity", ()):
            put(f"parity:{r['app']}", max_err=r["max_err"])
        thr = rec.get("throughput")
        if thr:
            put("throughput", req_per_s=thr["req_per_s"],
                deadline_miss_rate=thr["deadline_miss_rate"],
                speedup_vs_serial=thr.get("speedup_vs_serial"))
        mt = rec.get("multi_tenant")
        if mt:
            put("multi_tenant",
                lost=mt["light"]["lost"] + mt["light"]["turned_away"],
                light_miss_rate=mt["light"]["deadline_miss_rate"],
                ladder_transitions=(mt["hot"]["ladder_up"]
                                    + mt["hot"]["ladder_down"]),
                hot_absorbed=(mt["hot"]["ladder_shed"]
                              + mt["hot"]["throttled"]),
                watchdog_timeouts=mt["watchdog_timeouts"])
    else:  # unknown bench: record parity-bearing rows generically
        for section in rec.values():
            if isinstance(section, list):
                for i, r in enumerate(section):
                    if isinstance(r, dict) and "max_err" in r:
                        put(f"row:{i}", max_err=r["max_err"])
    return cases


def _floor_for(bench: str, case: str):
    for (b, pat), spec in FLOORS.items():
        if b == bench and fnmatch.fnmatch(case, pat):
            return spec
    return None


# --------------------------------------------------------------------------- #
# merge + check                                                                #
# --------------------------------------------------------------------------- #


def collect(results_dir: str = RESULTS_DIR) -> dict:
    """Read every full-mode BENCH_*.json (plus the committed serving smoke
    parity reference) into ``{bench: cases}``."""
    benches: dict = {}
    for path in sorted(glob.glob(os.path.join(results_dir, "BENCH_*.json"))):
        name = os.path.basename(path)[len("BENCH_"):-len(".json")]
        if name == "trajectory":
            continue
        if name.endswith("_smoke") and name not in (
            "serving_smoke", "robustness_smoke", "decode_smoke",
        ):
            continue  # smoke runs are CI plumbing, not perf data
        with open(path) as f:
            rec = json.load(f)
        cases = _cases_from(name, rec)
        if cases:
            benches[name] = cases
    return benches


def merge(pr: int, results_dir: str = RESULTS_DIR) -> dict:
    """Fold the current snapshots into the trajectory file under ``pr``."""
    path = os.path.join(results_dir, TRAJECTORY)
    traj = {"schema": 1, "entries": {}}
    if os.path.exists(path):
        with open(path) as f:
            traj = json.load(f)
    benches = collect(results_dir)
    for bench, cases in benches.items():
        for case, fields in cases.items():
            floor = _floor_for(bench, case)
            if floor:
                fields["floor"] = floor
    traj["entries"][str(pr)] = benches
    with open(path, "w") as f:
        json.dump(traj, f, indent=1, sort_keys=True)
    print(f"trajectory: PR {pr} merged ({sum(len(c) for c in benches.values())}"
          f" cases over {len(benches)} benches) -> {os.path.abspath(path)}")
    return traj


def check(traj: dict | None = None, results_dir: str = RESULTS_DIR) -> int:
    """Assert every floor over every recorded PR entry.  Returns the number
    of cases checked; raises AssertionError listing ALL violations."""
    if traj is None:
        path = os.path.join(results_dir, TRAJECTORY)
        with open(path) as f:
            traj = json.load(f)
    violations, checked = [], 0
    for pr, benches in sorted(traj["entries"].items(), key=lambda kv: int(kv[0])):
        for bench, cases in sorted(benches.items()):
            for case, fields in sorted(cases.items()):
                floor = _floor_for(bench, case)
                if floor is None:
                    continue
                checked += 1
                tag = f"PR {pr} {bench}/{case}"
                err = fields.get("max_err")
                if "max_err" in floor and err is not None and err > floor["max_err"]:
                    violations.append(f"{tag}: max_err {err:.3e} > {floor['max_err']:.0e}")
                ratio = fields.get("speedup")
                if ratio is not None:
                    if fields.get("mode") == "hw":
                        if ratio <= 1.0:  # hw-only gate: must beat baseline
                            violations.append(f"{tag}: hw speedup {ratio:.2f} <= 1.0")
                    elif "min_ratio" in floor and ratio < floor["min_ratio"]:
                        violations.append(
                            f"{tag}: interpret ratio {ratio:.2f} < floor "
                            f"{floor['min_ratio']}"
                        )
                steps = fields.get("plan_steps")
                if "max_steps" in floor and steps is not None and steps > floor["max_steps"]:
                    violations.append(f"{tag}: plan_steps {steps} > {floor['max_steps']}")
                if floor.get("zero_fallbacks") and fields.get("fallbacks"):
                    violations.append(f"{tag}: fallbacks {fields['fallbacks']}")
                over = fields.get("overhead")
                if "max_overhead" in floor and over is not None and over > floor["max_overhead"]:
                    violations.append(
                        f"{tag}: degraded overhead {over:.2f}x > "
                        f"{floor['max_overhead']}x"
                    )
                if floor.get("zero_lost") and fields.get("lost"):
                    violations.append(f"{tag}: {fields['lost']} lost requests")
                if floor.get("require_match") and fields.get("match") is False:
                    violations.append(
                        f"{tag}: greedy decode diverged from the jnp loop"
                    )
                if floor.get("require_fusion"):
                    su = fields.get("steps_unfused")
                    if steps is not None and su is not None and steps >= su:
                        violations.append(
                            f"{tag}: no plan-step reduction ({steps} >= {su})"
                        )
                if floor.get("zero_leak") and fields.get("leaked_pages"):
                    violations.append(
                        f"{tag}: {fields['leaked_pages']} KV pages leaked"
                    )
                if floor.get("require_survival") and fields.get("survived") is False:
                    violations.append(f"{tag}: scheduler thread died")
                if floor.get("require_bitexact") and fields.get("bitexact") is False:
                    violations.append(f"{tag}: total demotion not bit-exact")
                if floor.get("require_recovered") and fields.get("recovered") is False:
                    violations.append(f"{tag}: breakers did not recover")
                lmr = fields.get("light_miss_rate")
                if ("max_light_miss_rate" in floor and lmr is not None
                        and lmr > floor["max_light_miss_rate"]):
                    violations.append(
                        f"{tag}: in-SLO tenant miss rate {lmr:.3f} > "
                        f"{floor['max_light_miss_rate']}"
                    )
                if (floor.get("require_ladder")
                        and not fields.get("ladder_transitions")):
                    violations.append(
                        f"{tag}: no ladder transitions -- what absorbed the "
                        f"overload?"
                    )
                if floor.get("zero_watchdog") and fields.get("watchdog_timeouts"):
                    violations.append(
                        f"{tag}: {fields['watchdog_timeouts']} watchdog "
                        f"timeouts (the ladder, not the watchdog, must "
                        f"absorb overload)"
                    )
    if violations:
        raise AssertionError(
            "bench trajectory floor regressions:\n  " + "\n  ".join(violations)
        )
    print(f"trajectory: {checked} floors hold over "
          f"{len(traj['entries'])} PR entr{'y' if len(traj['entries']) == 1 else 'ies'}")
    return checked


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--merge", action="store_true",
                    help="fold the current BENCH_*.json snapshots into the "
                         "trajectory under --pr, then check")
    ap.add_argument("--pr", type=int, default=None,
                    help="PR number for --merge (required with --merge)")
    ap.add_argument("--check", action="store_true",
                    help="assert floors on the committed trajectory (CI)")
    ap.add_argument("--results-dir", default=RESULTS_DIR)
    args = ap.parse_args()
    if args.merge:
        if args.pr is None:
            ap.error("--merge requires --pr")
        traj = merge(args.pr, args.results_dir)
        check(traj, args.results_dir)
    elif args.check:
        check(results_dir=args.results_dir)
    else:
        ap.error("pass --merge --pr N or --check")


if __name__ == "__main__":
    main()
