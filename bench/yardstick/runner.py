"""One run of one cell: set up, measure for ``seconds``, compare with the
plain reference, and build the result line.

With ``trace=0`` the metrics are the cell's end-to-end metrics; with
``trace=1`` the profiler and the program's own spans are on through the
window, and the metrics are the cell's per-layer metrics, each read by its
own file under ``metrics/``.  Both compare the same way.
"""

from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
import types
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from . import device, trace as trace_mod

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Programs lowered and compiled while armed (there should be none in
    the window: every shape was warmed in set-up)."""

    def __init__(self):
        self.lowered = self.compiled = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if not self.armed:
            return
        if event == LOWERING_EVENT:
            self.lowered += 1
        elif event == COMPILE_EVENT:
            self.compiled += 1

    def close(self) -> None:
        self.armed = False
        try:
            jax.monitoring.unregister_event_duration_listener(self._on)
        except Exception:  # an older JAX keeps listeners for the process
            pass


class GcPauses:
    """The garbage collector's pauses from set-up's end to the window's."""

    def __init__(self):
        self.pauses: List[Tuple[int, float]] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((int(info.get("generation", -1)), time.perf_counter() - self._t))

    def close(self) -> None:
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)

    def line(self) -> str:
        ms = [1e3 * d for _, d in self.pauses]
        return (f"gc: collections={len(ms)} oldest_gen={sum(g == 2 for g, _ in self.pauses)} "
                f"max_ms={max(ms, default=0.0):.3f} total_ms={sum(ms):.3f}")


class DeviceTrace:
    """The profiler around the window, with the program's own spans on the
    same clock."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.marker_pc = None
        self.obs = None

    def start(self) -> None:
        from repro.obs import trace as otrace

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # on a TPU, annotations and not every runtime call; on a CPU (the
        # rehearsals) the operations are themselves host events
        opts.host_tracer_level = 1 if jax.default_backend() == "tpu" else 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.marker_pc = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_mod.MARKER):
            pass
        self.obs = otrace.start_tracing(clock=time.perf_counter)

    def stop(self) -> Tuple[trace_mod.Trace, List[Dict[str, Any]]]:
        from repro.obs import trace as otrace

        otrace.stop_tracing()
        jax.profiler.stop_trace()
        try:
            tr = trace_mod.load(self.dir, self.marker_pc)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        spans = [dict(s, ts=s["ts"] * 1e-6, dur=s["dur"] * 1e-6) for s in self.obs.spans()]
        return tr, spans


def _finite(v: float) -> Optional[float]:
    return float(v) if v is not None and math.isfinite(v) else None


def run(cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        interpret: bool = False, peaks_of: Optional[str] = None, err=sys.stderr,
        after: Optional[Callable[[Any], None]] = None) -> Dict[str, Any]:
    """Run ``cell`` once and return the result line's object.  Notes and
    the compared numbers go to ``err``; the numbers compared come last.
    ``interpret`` and ``peaks_of`` (a device kind whose peaks stand in) are
    for rehearsals off the chip only; ``after(driver)`` is called once the
    comparison is done (``calibrate.py`` reads the control there)."""
    drv = cell.driver()(cell, seed, interpret=interpret)
    drv.setup()
    # what set-up allocated stays: keep the collector from walking it
    # through the window, as a long-running server process would
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    pauses = GcPauses()
    counter = CompileCounter()
    tracer = DeviceTrace() if trace else None

    def on_start():
        if tracer is not None:
            tracer.start()
        counter.armed = True

    try:
        drv.measure(seconds, on_start)
    finally:
        counter.armed = False
        counter.close()
        pauses.close()
        gc.unfreeze()
    tr = spans = None
    if tracer is not None:
        tr, spans = tracer.stop()
    print(f"window: compilations={counter.lowered} backend_compiles={counter.compiled} "
          f"seconds={seconds}", file=err)
    print(pauses.line(), file=err)
    dev = device.describe()
    dev["memory_peak_bytes"] = device.memory_peak_bytes(cell.chips)
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        ctx = layer_context(cell, drv, tr, spans, peaks_of or dev["kind"])
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        t0, t1 = drv.window
        dev["busy_s"] = tr.busy(t0, t1)
        dev["window_s"] = t1 - t0
        breakdown = make_breakdown(tr, ctx.host_spans, t0, t1)
    else:
        values = drv.end_to_end()
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    lines = drv.notes()
    drv.release()
    checks = drv.compare()
    correct = all(c.ok for c in checks)
    for line in lines:
        print(line, file=err)
    for c in checks:
        print(c.line(), file=err)
    out = {
        "correct": correct, "attempted": int(drv.attempted), "failed": int(drv.failed),
        "metrics": metrics, "device": dev,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": _finite(c.value), "limit": c.limit} for c in checks}
    if after is not None:
        after(drv)
    return out


def layer_context(cell, drv, tr, spans, kind: str) -> types.SimpleNamespace:
    """What the metric readers read: the window, the reduced trace, host
    spans (the program's and the benchmark's own, on one clock), the
    driver's records, the config and the chip's peaks."""
    host = [(s["name"], s["ts"], s["ts"] + s["dur"]) for s in spans] + drv.host_spans(spans)
    return types.SimpleNamespace(
        window=drv.window, trace=tr, spans=spans, host_spans=host,
        peaks=device.peaks(kind), config=cell.config, traffic=cell.traffic,
        reference=drv.ref, driver=drv.layer_context(),
    )


def make_breakdown(tr, host_spans, t0: float, t1: float) -> Dict[str, List]:
    ops = trace_mod.time_by_name(tr.ops_in(t0, t1))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(tr.idle_gaps(t0, t1), key=lambda g: g[0] - g[1])[:10]
    longest = trace_mod.label_gaps(longest, host_spans)
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": [[k, v] for k, v in longest]}
