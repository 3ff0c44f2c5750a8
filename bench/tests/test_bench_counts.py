"""Operation and byte counts against hand counts, and the peaks table."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from yardstick import cells, counts, device  # noqa: E402


@pytest.fixture(scope="module")
def style():
    with open(os.path.join(BENCH, "configs", "style_transfer_512.json")) as f:
        cfg = json.load(f)
    return cells.load_module(os.path.join(BENCH, "configs", "style_transfer_512.py")), cfg


def test_pruned_style_transfer_frame_by_hand(style):
    ref, cfg = style
    px = 512 * 512
    hand = (
        2 * 32 * 3 * 49 * px                      # stem, image input never pruned
        + 2 * 64 * 16 * 9 * px // 4               # down0: 16 of 32 inputs kept, stride 2
        + 2 * 128 * 32 * 9 * px // 16             # down1: 32 of 64 kept
        + 5 * 2 * 128 * 64 * 1 * px // 16         # residual 1x1 entries: 64 of 128
        + 5 * 2 * 128 * 64 * 9 * px // 16         # residual 3x3 bodies
        + 2 * 64 * 64 * 9 * px // 4               # up0 at 256x256: 64 of 128 kept
        + 2 * 32 * 32 * 9 * px                    # up1 at 512x512: 32 of 64 kept
        + 2 * 3 * 16 * 49 * px                    # output conv: 16 of 32 kept
    )
    assert counts.frame_ops(ref, cfg) == hand
    assert 29.0e9 < hand < 29.4e9
    dense = dict(cfg, pruning=dict(cfg["pruning"], sparsity=0.0))
    assert 55.9e9 < counts.frame_ops(ref, dense) < 56.3e9


def test_conv_bytes_and_bound(style):
    ref, cfg = style
    convs = {c["name"]: c for c in counts.frame_convs(ref, cfg, 4)}
    stem = convs["conv_in"]
    assert stem["bytes"] == 4 * (4 * 3 * 512 * 512 + 32 * 3 * 49 + 32 + 4 * 32 * 512 * 512)
    peaks = device.peaks("TPU v5 lite")
    # with float32 activations even the stem moves more than it computes
    t, bound = counts.least_time(stem["ops"], stem["bytes"], peaks)
    assert bound == "memory" and t == pytest.approx(stem["bytes"] / 819e9)
    # a wide 3x3 conv at 64x64 computes more than it moves
    ops, nbytes = counts.conv(4, 512, 512, 64, 64, 3, 1)
    assert ops == 2 * 4 * 512 * 64 * 64 * 512 * 9
    t, bound = counts.least_time(ops, nbytes, peaks)
    assert bound == "compute" and t == pytest.approx(ops / 197e12)


def test_peaks_table_has_a_source_and_refuses_unknown_kinds():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        device.peaks("cpu")
