"""The benchmark's readers of the program's host spans: on a hand-built
context whose answers are worked by hand (a 10 s window, four stretches of
device work, six frames, four macro-batches, one after the window), and
in a tiny traced CPU rehearsal of the frame cell, which reports them."""

import io
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from yardstick import cells, runner, trace  # noqa: E402

HOST_SPAN_METRICS = ("submit_ms.stream", "dispatch_ms.stream", "resolve_ms.stream",
                     "idle_with_work.stream")

# device busy [0,2] [3,6] [6.5,7] [9,9.8]: idle [2,3] [6,6.5] [7,9] [9.8,10]
OPS = [(0.0, 2.0), (3.0, 3.0), (6.5, 0.5), (9.0, 0.8)]


def span(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args, "tid": 1, "cat": ""}


def batch(ts, dur, rids, call, resolve):
    return [span("batch", ts, dur, rids=rids), span("chunk.call", *call),
            span("batch.resolve", *resolve)]


SPANS = [
    span("chunk.call", 0.1, 0.05),  # a call outside any batch: no frame's
    span("submit", 0.5, 0.25, rid=0),  # admitted 0.75
    span("submit", 0.8, 0.1, rid=1),  # admitted 0.9
    *batch(1.0, 1.5, [0, 1], (1.2, 0.3), (2.0, 0.4)),  # dispatched 1.5
    span("submit", 2.2, 0.3, rid=2),  # admitted 2.5: waits over idle [2.5,3]
    *batch(2.8, 3.0, [2], (3.2, 0.2), (5.0, 0.6)),  # dispatched 3.4
    span("submit", 7.0, 0.5, rid=3),  # admitted 7.5: waits over idle [7.5,9]
    *batch(8.0, 1.9, [3], (8.5, 1.25), (9.0, 0.8)),  # dispatched 9.75
    span("submit", 9.5, 0.1, rid=4),  # admitted 9.6, never dispatched
    span("submit", 9.85, 0.05, rid=5),  # admitted 9.9, dispatched after
    *batch(10.5, 1.0, [5], (10.6, 0.1), (11.0, 0.3)),
]


def ctx(spans=SPANS):
    ops = [trace.Event("op", s, d, {}) for s, d in OPS]
    return types.SimpleNamespace(window=(0.0, 10.0), spans=spans,
                                 trace=trace.Trace(ops=ops, modules=[], host=[]))


def reader(name):
    return cells.find("style512_stream", ROOT).metric_reader(name)


def test_submit_ms_over_the_frames_window_batches_served():
    # rids 0-3 (rid 4 never served, rid 5 by a batch after the window)
    assert reader("submit_ms.stream")(ctx()) == pytest.approx(1e3 * (0.25 + 0.1 + 0.3 + 0.5) / 4)


def test_dispatch_ms_over_the_calls_inside_window_batches():
    assert reader("dispatch_ms.stream")(ctx()) == pytest.approx(1e3 * (0.3 + 0.2 + 1.25) / 3)


def test_resolve_ms_over_the_window_batches():
    assert reader("resolve_ms.stream")(ctx()) == pytest.approx(1e3 * (0.4 + 0.6 + 0.8) / 3)


def test_idle_with_work_counts_only_idle_time_under_waiting_frames():
    # idle [2,3]: rid 2 waits over [2.5,3] -> 0.5; idle [6,6.5]: no frame
    # waits; idle [7,9]: rid 3 waits over [7.5,9] -> 1.5; idle [9.8,10]:
    # rids 4 and 5 both wait, counted once -> 0.2
    got = reader("idle_with_work.stream")(ctx())
    assert got == pytest.approx(100.0 * (0.5 + 1.5 + 0.2) / 10.0)
    device_idle = reader("device_idle.stream")(ctx())
    assert device_idle == pytest.approx(100.0 * (1.0 + 0.5 + 2.0 + 0.2) / 10.0)
    assert got < device_idle


def test_a_program_without_the_host_spans_reads_nothing():
    """The parent program emits ``batch`` spans alone: each reader of the
    new spans reads nothing there, and raises nothing."""
    only_batches = [s for s in SPANS if s["name"] == "batch"]
    for name in HOST_SPAN_METRICS:
        assert reader(name)(ctx(only_batches)) is None, name


def test_the_traced_rehearsal_reports_the_host_span_metrics():
    cell = cells.find("style512_stream", ROOT)
    cell.config = cells.merged(cell.config, {"base_channels": 8, "residual_blocks": 1,
                                             "frame": [3, 16, 16]})
    cell.traffic = cells.merged(cell.traffic, {"streams": 2, "fps": 10, "pool": 3})
    out = runner.run(cell, 2**31 + 78, 1.0, True, t_start=time.perf_counter(),
                     interpret=True, peaks_of="TPU v5 lite", err=io.StringIO())
    assert out["correct"] is True and out["failed"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in HOST_SPAN_METRICS:
        assert name in m, name
    assert m["submit_ms.stream"] > 0 and m["dispatch_ms.stream"] > 0 and m["resolve_ms.stream"] > 0
    assert 0 <= m["idle_with_work.stream"] <= m["device_idle.stream"]
