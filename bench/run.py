"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration, traffic and
metrics are found by name through ``BENCHMARK.json``.  The process sets up
the system (weights from ``--seed``, plans, server, every shape warmed),
measures for ``--seconds``, compares what the timed path served with the
plain reference, and prints as the last line of standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` a ``breakdown``), then ``checks``.  The numbers
compared, each beside its limit, are also the last lines of standard error.

It refuses, with a non-zero exit and no result, a machine where JAX finds
no TPU or fewer chips than the cell asks for.  JAX's persistent compilation
cache lives in ``.jax_cache/`` at the root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _prepare() -> None:
    """Before JAX loads: the benchmark and the program on the path, the
    compile cache at its fixed place in the checkout (the program takes
    its cache from the same variable), the TPU runtime's logs off (they
    would go to a fixed /tmp path)."""
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _prepare()

    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # persist every compile, not only those over the default one second:
    # a small program left out would be compiled again in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from yardstick import cells, device, runner

    cell = cells.find(args.workload, ROOT)
    try:
        dev = device.require(cell.chips)
    except device.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(f"device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}", file=sys.stderr)
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
