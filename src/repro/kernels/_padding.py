"""Shared zero-padding helpers for the kernel ``ops`` wrappers.

One definition, so the per-step and whole-sequence LSTM paths can never
silently diverge in alignment semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def pad_axis_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` up to exactly ``size`` elements."""
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def pad_axis_to_multiple(x: jax.Array, mult: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple of ``mult``."""
    return pad_axis_to(x, round_up(x.shape[axis], mult), axis)


LANES = 128   # TPU vreg lane width: a block's last dim must be a multiple


def lane_tile(tile: int) -> int:
    """Width an engine tile occupies in the int8 kernels' VMEM layout: the
    silicon's tile (96 for Chipmunk) rounded up to whole 128-lane vregs."""
    return round_up(tile, LANES)


def pad_tiles(x: jax.Array, tile: int, axis: int) -> jax.Array:
    """Zero-pad every ``tile``-wide block of ``axis`` to ``lane_tile(tile)``.

    A pure relayout: the padded lanes hold zeros, so an integer MAC against
    zero-padded weights is exact and drops nothing."""
    tp = lane_tile(tile)
    if tp == tile:
        return x
    axis %= x.ndim
    n = x.shape[axis] // tile
    x = x.reshape(x.shape[:axis] + (n, tile) + x.shape[axis + 1:])
    x = pad_axis_to(x, tp, axis + 1)
    return x.reshape(x.shape[:axis] + (n * tp,) + x.shape[axis + 2:])


def unpad_tiles(x: jax.Array, tile: int, axis: int) -> jax.Array:
    """Inverse of ``pad_tiles``: keep the first ``tile`` lanes of each block."""
    tp = lane_tile(tile)
    if tp == tile:
        return x
    axis %= x.ndim
    n = x.shape[axis] // tp
    x = x.reshape(x.shape[:axis] + (n, tp) + x.shape[axis + 1:])
    x = jax.lax.slice_in_dim(x, 0, tile, axis=axis + 1)
    return x.reshape(x.shape[:axis] + (n * tile,) + x.shape[axis + 2:])
