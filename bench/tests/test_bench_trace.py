"""The trace reduction on a small committed trace recorded on the CPU:
three runs of a jitted program, a 50 ms host wait inside a
``bench.host_wait`` annotation, three more runs.  On the CPU the XLA
operations run on host threads, which the reduction takes as the device."""

import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from yardstick import stats, trace  # noqa: E402

DATA = os.path.join(HERE, "data", "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def tr(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    os.makedirs(d / "plugins" / "profile" / "run")
    shutil.copy(DATA, d / "plugins" / "profile" / "run" / "t.xplane.pb")
    return trace.load(str(d), marker_pc=100.0)


def test_ops_are_the_programs_operations(tr):
    assert len(tr.ops) == 24  # 6 runs x 4 operations
    assert {e.stats["hlo_module"] for e in tr.ops} == {"jit__lambda"}
    assert trace.stable_name(tr.ops[0]) == "dot_general"
    by_name = trace.time_by_name(tr.ops)
    assert set(by_name) == {"dot_general", "wrapped_tanh", "wrapped_reduce-window", "wrapped_reduce"}
    assert sum(by_name.values()) == pytest.approx(sum(e.dur for e in tr.ops))


def test_events_sit_on_the_marker_clock(tr):
    marker = [e for e in tr.host if e.name == trace.MARKER]
    assert len(marker) == 1 and marker[0].start == pytest.approx(100.0)
    assert all(e.start > 100.0 for e in tr.ops)


def test_busy_and_idle(tr):
    lo = min(e.start for e in tr.ops)
    hi = max(e.end for e in tr.ops)
    busy = tr.busy(lo, hi)
    assert 0 < busy < 0.002
    idle = tr.idle_gaps(lo, hi)
    assert sum(b - a for a, b in idle) == pytest.approx(hi - lo - busy)
    longest = max(idle, key=lambda g: g[1] - g[0])
    assert 0.045 < longest[1] - longest[0] < 0.2


def test_gaps_are_labelled_by_the_open_host_span(tr):
    lo = min(e.start for e in tr.ops)
    hi = max(e.end for e in tr.ops)
    spans = [(e.name, e.start, e.end) for e in tr.host if e.name.startswith("bench.")]
    labelled = trace.label_gaps(tr.idle_gaps(lo, hi), spans)
    name, secs = max(labelled, key=lambda kv: kv[1])
    assert name == "bench.host_wait" and secs > 0.045
    assert {n for n, s in labelled if s < 0.001} == {"no_host_span"}


def test_interval_arithmetic_by_hand():
    m = stats.merge([(5, 6), (0, 2), (1, 3), (8, 9)])
    assert m == [(0, 3), (5, 6), (8, 9)]
    assert stats.covered(m, 1, 8.5) == 2 + 1 + 0.5
    assert stats.gaps(m, -1, 10) == [(-1, 0), (3, 5), (6, 8), (9, 10)]
    assert stats.percentile([5, 1, 4, 2, 3], 50) == 3
    assert stats.percentile(list(range(1, 101)), 99) == 99
