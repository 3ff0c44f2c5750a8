"""A tiny CPU rehearsal of the frame cell through the harness's test hook
(``runner.run`` with interpret-mode kernels), the refusal of the CPU by the
measurement entry, a served frame altered where it is produced, and the
lower-precision control, all at a size a test run holds."""

import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from yardstick import cells, runner  # noqa: E402

TINY = {"base_channels": 8, "residual_blocks": 1, "frame": [3, 16, 16]}
TRICKLE = {"streams": 2, "fps": 10, "pool": 3}
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture(autouse=True, scope="module")
def _restore_program_state():
    """The rehearsals drive the program in the test process: put back the
    process-wide state they touch (metrics registry, tracing switch, tuning
    cache, fault plans) once the file is done, for the files that share the
    worker."""
    from repro.kernels import ops as kops
    from repro.obs import metrics
    from repro.obs import trace as otrace
    from repro.robustness import faults

    cache = kops.tuning_cache()
    snap = (metrics.registry().dump_state(), otrace.state(), dict(cache.entries), cache.enabled,
            cache.sweeps, cache.path, cache.ops_filter, {k: dict(v) for k, v in cache.stats.items()})
    try:
        yield
    finally:
        faults.uninstall_all()
        metrics.registry().load_state(snap[0])
        otrace.restore(snap[1])
        (cache.entries, cache.enabled, cache.sweeps, cache.path, cache.ops_filter,
         cache.stats) = snap[2:]


def tiny_cell():
    cell = cells.find("style512_stream", ROOT)
    cell.config = cells.merged(cell.config, TINY)
    cell.traffic = cells.merged(cell.traffic, TRICKLE)
    return cell


def rehearse(trace, after=None, cell=None):
    out = runner.run(cell or tiny_cell(), 2**31 + 77, 1.0, trace, t_start=time.perf_counter(),
                     interpret=True, peaks_of="TPU v5 lite", after=after)
    return json.loads(json.dumps(out))  # the line as the driver reads it


@pytest.fixture(scope="module")
def traced():
    box = {}

    def after(drv):
        box["program"] = {c.name: c.value for c in drv.compare()}
        box["control"] = drv.control()
        box["limits"] = list(drv.cfg["limits"])

    return rehearse(True, after), box


def test_traced_line_has_the_contract_keys_and_layer_metrics(traced):
    out, _ = traced
    assert set(out) == KEYS | {"breakdown"} and list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 20
    assert out["device"]["platform"] == "cpu" and out["device"]["window_s"] == 1.0
    assert 0 < out["device"]["busy_s"] <= 1.0
    for name in ("frame_p99_ms.stream", "frame_queue_ms.stream", "frame_batch_fill.stream",
                 "frame_device_ms.stream", "device_idle.stream"):
        assert name in out["metrics"], name
    assert 0 < out["metrics"]["frame_batch_fill.stream"]["value"] <= 100
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10


def test_the_control_fails_where_the_program_passes(traced):
    out, box = traced
    # the CPU contracts float32 operands exactly: the program is the exact
    # reference, one unit of the stated precision's rounding from the
    # reference at that precision
    assert abs(box["program"]["frame_rms_ratio"] - 1.0) < 0.01
    assert box["program"] == {k: v["value"] for k, v in out["checks"].items()}
    control = {c.name: c for c in box["control"]}
    assert not all(c.ok for c in box["control"])  # the control is not correct,
    assert not all(control[n].ok for n in box["limits"])  # by its error
    assert control["frames_compared"].ok and control["failed"].ok  # on the same sample


def test_a_frame_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.core.graph import executor

    real = executor.BatchedPlan.run_chunk

    def altered(self, params, *inputs):
        out = real(self, params, *inputs)
        return out.at[0, 0, 0, 0].add(10.0)

    monkeypatch.setattr(executor.BatchedPlan, "run_chunk", altered)
    out = rehearse(False)
    assert set(out) == KEYS and list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"frame_p95_ms", "setup_s"}
    assert out["correct"] is False and out["checks"]["frame_err"]["value"] > 0.1


class ClosedLoop:
    """Three clients, each sending its next frame once its last is answered."""

    @staticmethod
    def schedule(p, seed, seconds):
        from yardstick.traffic import Request

        return [Request(0.0, i - 3 if i >= 3 else -1, i % 3, i % 3) for i in range(9)]


def test_a_closed_loop_sends_each_frame_after_the_answer_it_waits_for():
    cell = tiny_cell()
    cell.arrivals = lambda: ClosedLoop
    box = {}
    out = rehearse(False, lambda drv: box.update(drv.raw), cell)
    assert out["correct"] is True and out["attempted"] == 9 and out["failed"] == 0
    ready, lat = box["ready"], box["latency_s"]
    assert (lat > 0).all()
    for i, r in enumerate(box["sched"]):
        if r.after >= 0:  # ready no sooner than the answer it waited for
            assert ready[i] >= ready[r.after] + lat[r.after]


def test_the_measurement_entry_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "style512_stream", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT,
    )
    assert res.returncode != 0
    assert "{" not in res.stdout
    assert "no TPU" in res.stderr
