"""The least time of one chunk call, from the operations and bytes that
the configuration's model kind counts (``models/<kind>.py``)."""
from __future__ import annotations


def chunk_least_s(kind, model: dict, frames: int, streams: int, peaks: dict,
                  itemsize: int = 4) -> float:
    """The least time the chip could take for one chunk call's useful work:
    the larger of its FLOPs over the bf16 peak (the precision the chip's
    matrix unit runs float32 products at by default) and its bytes over
    the HBM bandwidth."""
    compute = frames * kind.flops_per_frame(model) / peaks['bf16_flops_per_s']
    return max(compute, kind.chunk_bytes(model, frames, streams, itemsize)
               / peaks['hbm_bytes_per_s'])
