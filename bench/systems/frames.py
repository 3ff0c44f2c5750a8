"""Driver of frame cells: an image-to-image app served through the
program's normal path, fed by the mix's arrival model.

Set-up builds the system the way a deployment does: the config's seeded,
column-pruned weights (made on the device by the plain reference's
``init``) go into the app graph, ``app_masks`` -> ``PassManager`` ->
``compile_plan(backend="kernel")`` -> ``AsyncPlanServer.add_plan``.  Every
batch size the window can form (1 to the batch) is warmed, so nothing
compiles in the window.

The window sends every request once it is ready (its due time, and the
answer it waits for, if any) with the mix's deadline, from a pool of
seeded host frames (the host-to-device copy is on the served path), and a
client thread takes each output into host memory.  Latency runs from the
moment a frame was ready to the moment the client holds its output.  A
frame that fails or is refused never arrives: its latency is the time the
client gave up waiting.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import compare, counts, traffic
from yardstick.stats import percentile

#: seconds past the window's close the client waits for the last answers
GRACE_S = 60.0
#: frames of the window whose outputs are kept and compared
SAMPLE = 64
#: a heartbeat gap or a ``submit`` call longer than this is noted
SLOW_S = 0.03


def _events(events) -> str:
    """``(seconds into the window, seconds, ...)`` tuples as one short field:
    the count and the ten longest, in milliseconds after the first."""
    top = sorted(events, key=lambda e: -e[1])[:10]
    return f"{len(events)} " + " ".join(
        "(" + ",".join([f"{e[0]:.2f}"] + [f"{1e3 * v:.1f}" for v in e[1:]]) + ")" for e in top)


class Driver:
    def __init__(self, cell, seed: int, *, interpret: bool = False):
        self.cell, self.seed, self.interpret = cell, int(seed), interpret
        self.cfg, self.tp = cell.config, cell.traffic
        self.arrivals = cell.arrivals()
        self.ref = cell.reference()
        self.name = self.cfg["program"]["app"]
        self.shape = tuple(int(v) for v in self.cfg["frame"])
        self.batch = int(self.cfg["serving"]["batch_size"])

    # ------------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core.graph import PassContext, PassManager, compile_plan
        from repro.models.cnn import APPS, app_masks
        from repro.serving import AsyncPlanServer

        cfg = self.cfg
        self.params = jax.jit(lambda k: self.ref.init(k, cfg))(compare.prng_key(self.seed))
        g = APPS[self.name](
            jax.random.PRNGKey(0), base=int(cfg["base_channels"]), n_res=int(cfg["residual_blocks"])
        )
        compare.check_same_tree(g.params, self.params, "app graph")
        g = dataclasses.replace(g, params={k: dict(v) for k, v in self.params.items()})
        masks, structures = app_masks(g, self.name, sparsity=float(cfg["pruning"]["sparsity"]))
        go = PassManager().run(g, PassContext(masks=masks, structures=structures))
        plan = compile_plan(go, backend=cfg["program"]["backend"], interpret=self.interpret)
        sv = cfg["serving"]
        self.server = AsyncPlanServer(
            flush_after=sv.get("flush_after_s"), deadline_margin=float(sv["deadline_margin_s"]),
        )
        self.server.add_plan(self.name, plan, go.params, self.batch,
                             input_spec=[(self.shape, jnp.float32)])
        self.server.start()
        self.pool = traffic.input_pool(self.tp, self.seed, self.shape)
        deadline = float(self.tp["deadline_s"])
        for size in list(range(self.batch, 0, -1)) * 2:  # every batch the window can form
            hs = [self.server.submit(self.name, self.pool[i % len(self.pool)], deadline=deadline)
                  for i in range(size)]
            for h in hs:
                np.asarray(h.result())

    # ------------------------------------------------------------------ window
    def measure(self, seconds: float, on_start=None) -> None:
        sched = traffic.schedule(self.arrivals, self.tp, self.seed, seconds)
        n = len(sched)
        pick = traffic.rng(self.seed, 5)
        self.sample = set(int(i) for i in pick.choice(n, size=min(n, SAMPLE), replace=False))
        deadline = float(self.tp["deadline_s"])
        ready_at = np.full(n, np.nan)
        submit_at = np.full(n, np.nan)
        done_at = np.full(n, np.nan)
        rids = np.full(n, -1)
        answered = {r.after: threading.Event() for r in sched if r.after >= 0}
        kept: Dict[int, np.ndarray] = {}
        errors: List[str] = []
        todo: "queue.Queue" = queue.Queue()
        stats0 = self.server.stats["per_plan"][self.name]
        if on_start is not None:
            on_start()
        t0 = time.perf_counter() + 0.02
        give_up = t0 + seconds + GRACE_S

        def answer(i: int) -> None:
            if i in answered:
                answered[i].set()

        slow_submits: List[tuple] = []
        stalls: List[tuple] = []
        stop = threading.Event()

        def generate():
            for i, r in enumerate(sched):
                ready = t0 + r.due
                if r.after >= 0:
                    answered[r.after].wait(max(0.0, give_up - time.perf_counter()))
                    ready = max(ready, time.perf_counter())
                wait = ready - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                ready_at[i] = ready
                submit_at[i] = time.perf_counter()
                try:
                    h = self.server.submit(self.name, self.pool[r.item], deadline=deadline)
                    rids[i] = h.rid
                    todo.put((i, h))
                except Exception as e:  # refused: never arrives
                    errors.append(f"frame {i} refused: {e!r}")
                    answer(i)
                took = time.perf_counter() - submit_at[i]
                if took > SLOW_S:
                    slow_submits.append((submit_at[i] - t0, took))
            todo.put(None)

        def heartbeat():
            """Wakes every 2 ms: a longer gap means this process stood
            still (the process's CPU time tells a held interpreter from a
            machine that did not run it)."""
            last, cpu = time.perf_counter(), time.process_time()
            while not stop.is_set():
                time.sleep(0.002)
                now, c = time.perf_counter(), time.process_time()
                if now - last > SLOW_S:
                    stalls.append((last - t0, now - last, c - cpu))
                last, cpu = now, c

        def collect():
            while True:
                item = todo.get()
                if item is None:
                    return
                i, h = item
                try:
                    out = np.asarray(h.result(timeout=max(0.0, give_up - time.perf_counter())))
                except Exception as e:  # failed or never came
                    errors.append(f"frame {i} (rid {h.rid}) failed: {e!r}")
                    answer(i)
                    continue
                done_at[i] = time.perf_counter()
                answer(i)
                if i in self.sample:
                    kept[i] = out
                # the server keeps its last 4096 answers, device buffers
                # and all, until a caller drains them: a streaming client
                # that holds its own answers drains as it goes
                self.server.drain_completed()

        beat = threading.Thread(target=heartbeat, name="bench-heartbeat")
        beat.start()
        threads = [threading.Thread(target=generate, name="bench-generator"),
                   threading.Thread(target=collect, name="bench-client")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_end = time.perf_counter()
        stop.set()
        beat.join()
        stats1 = self.server.stats["per_plan"][self.name]
        missing = np.isnan(done_at)
        ready_at = np.where(np.isnan(ready_at), t_end, ready_at)
        self.window = (t0, t0 + seconds)
        self.raw = {
            "sched": sched, "ready": ready_at, "rids": rids,
            "latency_s": np.where(missing, t_end, done_at) - ready_at,
            "late_s": submit_at - ready_at, "kept": kept, "errors": errors,
            "slow_submits": slow_submits, "stalls": stalls,
            "stats": {k: stats1[k] - stats0[k] for k in stats1},
        }
        self.attempted, self.failed = n, int(missing.sum())

    # ------------------------------------------------------------------ after
    def release(self) -> None:
        self.server.close()
        del self.server

    def notes(self) -> List[str]:
        late = self.raw["late_s"]
        late = late[~np.isnan(late)]
        lat_ms = 1e3 * self.raw["latency_s"]
        st = self.raw["stats"]
        lines = [
            f"frames: attempted={self.attempted} failed={self.failed} "
            f"p50_ms={percentile(lat_ms, 50):.3f} p90_ms={percentile(lat_ms, 90):.3f} "
            f"p95_ms={percentile(lat_ms, 95):.3f} p99_ms={percentile(lat_ms, 99):.3f} "
            f"max_ms={float(np.max(lat_ms)):.3f}",
            f"generator: late_ms p50={1e3 * percentile(late, 50):.3f} "
            f"p99={1e3 * percentile(late, 99):.3f} max={1e3 * float(np.max(late)):.3f}",
            f"server: batches={st['batches']} padded_frames={st['padded_frames']} "
            f"deadline_flushes={st['deadline_flushes']} deadline_misses={st['deadline_misses']}",
            "host: stalls over 30 ms (at_s, ms, process_cpu_ms)="
            + _events(self.raw["stalls"]),
            "generator: submits over 30 ms (at_s, ms)=" + _events(self.raw["slow_submits"]),
        ]
        lines += self.raw["errors"][:5]
        return lines

    def end_to_end(self) -> Dict[str, float]:
        return {"frame_p95_ms": percentile(1e3 * self.raw["latency_s"], 95)}

    # -------------------------------------------------------------- comparison
    def reference_frames(self, precision: Dict[str, str]) -> Dict[int, np.ndarray]:
        """The plain reference, at ``precision`` (``storage`` and
        ``operands`` dtypes), on every pool frame the sample uses, one at a
        time, after the window."""
        need = sorted({self.raw["sched"][i].item for i in self.raw["kept"]})
        dtype, operands = (getattr(jnp, precision[k]) for k in ("storage", "operands"))
        fwd = jax.jit(lambda p, x: self.ref.forward(p, x, self.cfg, dtype=dtype, operand_dtype=operands))
        with jax.default_matmul_precision("highest"):  # exact products of the rounded operands
            return {pi: np.asarray(fwd(self.params, jnp.asarray(self.pool[pi][None])))[0] for pi in need}

    def compare(self, served: Optional[Dict[int, np.ndarray]] = None) -> List[compare.Check]:
        """What the window served (or ``served`` in its place), each sampled
        frame against the reference at the precision the configuration
        states, on its pool frame, by every number the configuration's
        ``limits`` name (``compare.frame_numbers``; ``frame_rms_ratio`` also
        runs the reference at its ``exact`` precision), with every sampled
        frame compared and none failed."""
        served = self.raw["kept"] if served is None else served
        prec, limits = self.cfg["precision"], self.cfg["limits"]
        items = [self.raw["sched"][i].item for i in served]
        want = self.reference_frames(prec["reference"])
        exact = None
        if "frame_rms_ratio" in limits:
            ex = self.reference_frames(prec["exact"])
            exact = [ex[k] for k in items]
        nums = compare.frame_numbers(list(served.values()), [want[k] for k in items], exact)
        checks = [compare.Check(name, nums[name], float(limit)) for name, limit in limits.items()]
        return checks + [
            compare.Check("frames_compared", float(len(served)), float(len(self.sample)), at_least=True),
            compare.Check("failed", float(self.failed), 0.0),
        ]

    def control(self) -> List[compare.Check]:
        """The configuration's control -- the reference at the precision one
        step below what the check holds the program to -- in the program's
        place, on the same sample, judged by :meth:`compare`."""
        low = self.reference_frames(self.cfg["precision"]["control"])
        return self.compare({i: low[self.raw["sched"][i].item] for i in self.raw["kept"]})

    # ------------------------------------------------------------------ layers
    def host_spans(self, program_spans) -> List[tuple]:
        """``bench.frame_queued`` from each frame's submit to the start of
        its macro-batch: an idle gap under one had work waiting."""
        start = {r: s["ts"] for s in program_spans if s["name"] == "batch"
                 for r in s["args"].get("rids", ())}
        sub = self.raw["late_s"] + self.raw["ready"]
        return [("bench.frame_queued", float(sub[i]), start[int(r)])
                for i, r in enumerate(self.raw["rids"]) if int(r) in start]

    def layer_context(self) -> Dict[str, Any]:
        ref, cfg = self.ref, self.cfg
        return {
            "frame_ready": {int(r): float(d) for r, d in zip(self.raw["rids"], self.raw["ready"]) if r >= 0},
            "latency_ms": [float(v) for v in 1e3 * self.raw["latency_s"]],
            "server_stats": self.raw["stats"],
            "batch_size": self.batch,
            "frame_ops": counts.frame_ops(ref, cfg),
            "batch_convs": counts.frame_convs(ref, cfg, self.batch),
            "plan_name": self.name,
        }
