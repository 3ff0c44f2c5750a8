"""Operations and bytes the algorithms need, from shapes alone, and the
least time the chip could take for them.

These are the yardstick's counts: they come from the configuration (its
layers and the pruning it states), never from the program's plan, so a
change to the program cannot change what is counted.  An operation is a
multiply or an add (a multiply-accumulate is 2).  Bytes are float32 (4 per
element), read once and written once.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4


def conv_out(size: int, stride: int) -> int:
    """Output side of a zero-"SAME"-padded conv."""
    return -(-size // stride)


def conv(n: int, c_in: int, c_out: int, h_in: int, w_in: int, kernel: int, stride: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one conv over ``n`` frames: input, filter
    and output each moved once."""
    ho, wo = conv_out(h_in, stride), conv_out(w_in, stride)
    ops = 2.0 * n * c_out * ho * wo * c_in * kernel * kernel
    nbytes = F32 * (n * c_in * h_in * w_in + c_out * c_in * kernel * kernel + c_out + n * c_out * ho * wo)
    return ops, nbytes


def least_time(ops: float, nbytes: float, peaks: Dict[str, Any]) -> Tuple[float, str]:
    """The larger of operations over the bf16 peak (the f32 contractions run
    as one bf16 pass at the TPU's default precision) and bytes over the
    memory bandwidth, with which of the two bounds it."""
    t_ops = ops / float(peaks["bf16_flops"])
    t_mem = nbytes / float(peaks["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


# --------------------------------------------------------------------------- #
# frame model                                                                  #
# --------------------------------------------------------------------------- #


def frame_convs(ref, cfg: Dict[str, Any], n: int) -> List[Dict[str, Any]]:
    """Every conv of the pruned frame model over ``n`` frames, with its
    kept input channels: ``{name, ops, bytes}``.  ``ref`` is the config's
    plain reference module (it lists the layers)."""
    _, h, w = (int(v) for v in cfg["frame"])
    out = []
    for name, ci, co, k, stride, up in ref.convs(cfg):
        h, w = h * up, w * up
        kept = ref.kept_channels(cfg, ci)
        ops, nbytes = conv(n, kept, co, h, w, k, stride)
        out.append({"name": name, "ops": ops, "bytes": nbytes})
        h, w = conv_out(h, stride), conv_out(w, stride)
    return out


def frame_ops(ref, cfg: Dict[str, Any]) -> float:
    """Operations one frame of the pruned model requires (its convs; the
    norms and activations add well under 1%)."""
    return sum(c["ops"] for c in frame_convs(ref, cfg, 1))

