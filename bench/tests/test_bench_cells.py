"""Traffic from the seed, cells found by name, and ``BENCHMARK.json``
within the limits its readers hold it to."""

import json
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from yardstick import cells, traffic  # noqa: E402

STREAMS = {"arrivals": "periodic_streams", "streams": 4, "fps": 30, "phase_jitter": 1.0,
           "deadline_s": 0.033, "pool": 16}
BIG_SEED = 2**31 + 12345
STREAM_MODEL = cells.load_module(os.path.join(BENCH, "traffic", "periodic_streams.py"))


def stream_schedule(p, seed, seconds):
    return traffic.schedule(STREAM_MODEL, p, seed, seconds)


def test_same_seed_same_schedule_and_frames():
    a = stream_schedule(STREAMS, BIG_SEED, 10.0)
    assert a == stream_schedule(STREAMS, BIG_SEED, 10.0)
    assert a != stream_schedule(STREAMS, BIG_SEED + 1, 10.0)
    fa = traffic.input_pool(dict(STREAMS, pool=2), BIG_SEED, (3, 8, 8))
    fb = traffic.input_pool(dict(STREAMS, pool=2), BIG_SEED, (3, 8, 8))
    assert all(np.array_equal(x, y) for x, y in zip(fa, fb))


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_every_seed_sends_the_same_frames_in_another_phase(seed):
    sched = stream_schedule(STREAMS, seed, 10.0)
    assert len(sched) == 4 * 300  # 4 streams x 30 fps x 10 s
    assert all(r.after == -1 for r in sched)  # open loop
    dues = [r.due for r in sched]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 10.0
    for s in range(4):  # each stream is periodic at 30 fps
        mine = np.array([r.due for r in sched if r.stream == s])
        assert np.allclose(np.diff(mine), 1 / 30)
    phases = sorted(min(r.due for r in sched if r.stream == s) for s in range(4))
    assert all(p < (k + 1) / 4 / 30 for k, p in enumerate(phases))  # one stream per slot


@pytest.mark.parametrize("seed", [7, BIG_SEED])
def test_the_cells_mix_sends_every_seed_the_same_arrivals(seed):
    mix = json.load(open(os.path.join(BENCH, "traffic", "video30_4streams.json")))
    base = stream_schedule(mix, 0, 10.0)
    sched = stream_schedule(mix, seed, 10.0)
    assert [r.due for r in sched] == [r.due for r in base]  # the same moments
    assert [r.item for r in sched] != [r.item for r in base]  # other inputs
    assert np.allclose(np.diff([r.due for r in sched]), 1 / 120)  # evenly staggered


def test_frame_numbers_by_hand():
    from yardstick import compare

    want = [np.array([1.0, -2.0, 2.0, 0.0])]  # max |want| 2, 2-norm 3
    exact = [np.array([1.0, -2.0, 2.0, 0.6])]  # 0.6 / 3 = 0.2 off
    got = [np.array([1.0, -2.0, 2.3, 0.0])]  # 0.3 / 2 peak, 0.3 / 3 = 0.1 off
    n = compare.frame_numbers(got, want, exact)
    assert n["frame_err"] == pytest.approx(0.15)
    assert n["frame_rms_err"] == pytest.approx(0.1)
    assert n["frame_rms_ratio"] == pytest.approx(0.5)
    assert "frame_rms_ratio" not in compare.frame_numbers(got, want)
    assert np.isnan(compare.frame_numbers(got, want, want)["frame_rms_ratio"])  # no scale
    assert np.isnan(compare.frame_numbers([], [])["frame_err"])  # nothing compared


@pytest.mark.parametrize("bad", [
    [traffic.Request(0.5, -1, 0), traffic.Request(0.2, -1, 0)],  # out of order
    [traffic.Request(0.1, 1, 0), traffic.Request(0.2, -1, 0)],  # waits for a later one
    [traffic.Request(0.1, -1, 16)],  # not in the pool
    [traffic.Request(1.0, -1, 0)],  # past the window
])
def test_a_schedule_out_of_its_limits_is_refused(bad):
    model = type("M", (), {"schedule": staticmethod(lambda p, seed, seconds: bad)})
    with pytest.raises(ValueError):
        traffic.schedule(model, STREAMS, 1, 1.0)


CLOSED_LOOP = """
from yardstick.traffic import Request


def schedule(p, seed, seconds):
    n = int(p["clients"])
    return [Request(0.0, i - n if i >= n else -1, i % int(p["pool"]), i % n)
            for i in range(int(p["requests"]))]
"""


def test_a_cell_and_metric_added_as_files_are_found_by_name(tmp_path):
    """A later PR adds a configuration, a system, a mix with a new arrival
    model, and a metric as files and entries; the harness finds them with
    no edit to its code."""
    bench = tmp_path / os.path.basename(BENCH)
    for d in ("configs", "traffic", "metrics", "systems"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "tiny.json").write_text(json.dumps({"system": "echo", "frame": [3, 8, 8]}))
    (bench / "configs" / "tiny.py").write_text("def init(key, cfg):\n    return 'tiny weights'\n")
    (bench / "systems" / "echo.py").write_text("class Driver:\n    system = 'echo'\n")
    (bench / "traffic" / "closed_loop.py").write_text(CLOSED_LOOP)
    (bench / "traffic" / "backlog8.json").write_text(json.dumps(
        {"arrivals": "closed_loop", "clients": 8, "requests": 20, "pool": 3}))
    (bench / "metrics" / "tiny_share.py").write_text("def read(ctx):\n    return 42.0\n")
    rel = os.path.relpath(BENCH, ROOT)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": f"{rel}/configs/tiny.json"}],
        "workloads": [{"name": "tiny.backlog8", "config": "tiny", "traffic": "backlog8", "chips": 1}],
        "end_to_end": [{"name": "frame_p99_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "tiny_share", "unit": "%", "moves": "frame_p99_ms",
                       "workloads": ["tiny.backlog8"]},
                      {"name": "other", "unit": "%", "moves": "frame_p99_ms", "workloads": ["x"]}],
    }))
    cell = cells.find("tiny.backlog8", str(tmp_path))
    assert cell.traffic["clients"] == 8 and cell.config["frame"] == [3, 8, 8]
    assert cell.reference().init(None, None) == "tiny weights"
    assert cell.driver().system == "echo"
    sched = traffic.schedule(cell.arrivals(), cell.traffic, BIG_SEED, 1.0)
    assert len(sched) == 20 and [r.after for r in sched[7:10]] == [-1, 0, 1]
    assert [m["name"] for m in cell.end_to_end] == ["frame_p99_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["tiny_share"]
    assert cell.metric_reader("tiny_share")(None) == 42.0
    with pytest.raises(KeyError):
        cells.find("nope", str(tmp_path))


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_holds_to_its_limits():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and b["paths"] == [os.path.relpath(BENCH, ROOT)]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in b[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    cfgs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.splitext(os.path.join(ROOT, c["file"]))[0] + ".py")
        with open(os.path.join(ROOT, c["file"])) as f:
            assert sorted(json.load(f)["reduced"]) == sorted(c["reduced"])
    cells_by_name = {w["name"]: w for w in b["workloads"]}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["config"] in cfgs
        assert w["chips"] == 1 and len(w["why"]) <= 200
        cell = cells.find(w["name"], ROOT)
        assert traffic.schedule(cell.arrivals(), cell.traffic, BIG_SEED, float(b["run_seconds"]))
        assert cell.driver()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and m["better"] in ("lower", "higher")
    layers = {}
    for m in b["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        for w in m["workloads"]:
            assert w in cells_by_name
            reported = e2e[m["moves"]].get("workloads", list(cells_by_name))
            assert w in reported
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in cells_by_name:  # every cell reports setup_s, another end-to-end metric and a layer
        assert any(w in m.get("workloads", [w]) and n != "setup_s" for n, m in e2e.items())
        assert any(w in m["workloads"] for m in b["per_layer"])
