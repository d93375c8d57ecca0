"""Operations and bytes of a peephole-LSTM CTC model, from its shapes.

FLOPs count the matrix products (two per multiply-add): per frame, each
layer's input and recurrent products for four gates, and the read-out.
The element-wise gate arithmetic (about 20 operations per hidden unit and
layer, 0.3% of the products at 421 hidden) is left out.
"""
from __future__ import annotations


def flops_per_frame(model: dict) -> int:
    x, h, n, o = (model[k] for k in ('inputs', 'hidden', 'layers', 'outputs'))
    macs = 4 * h * (x + h) + (n - 1) * 4 * h * (2 * h) + o * h
    return 2 * macs


def weight_bytes(model: dict, itemsize: int = 4) -> int:
    x, h, n, o = (model[k] for k in ('inputs', 'hidden', 'layers', 'outputs'))
    per_layer = lambda fan_in: 4 * h * fan_in + 4 * h * h + 3 * h + 4 * h
    return itemsize * (per_layer(x) + (n - 1) * per_layer(h) + o * h + o)


def chunk_bytes(model: dict, frames: int, streams: int,
                itemsize: int = 4) -> int:
    """The least HBM traffic of one chunk call: the weights once, each
    valid frame's input and log-probs, and each active stream's (h, c) per
    layer read and written."""
    x, h, n, o = (model[k] for k in ('inputs', 'hidden', 'layers', 'outputs'))
    return (weight_bytes(model, itemsize)
            + itemsize * frames * (x + o)
            + itemsize * streams * n * 2 * h * 2)


def chunk_least_s(model: dict, frames: int, streams: int, peaks: dict,
                  itemsize: int = 4) -> float:
    """The least time the chip could take for one chunk call's useful work:
    the larger of its FLOPs over the bf16 peak (the precision the chip's
    matrix unit runs float32 products at by default) and its bytes over
    the HBM bandwidth."""
    return max(frames * flops_per_frame(model) / peaks['bf16_flops_per_s'],
               chunk_bytes(model, frames, streams, itemsize)
               / peaks['hbm_bytes_per_s'])
