"""Public op: quantized linear with automatic padding + calibration helpers."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core import quant
from .._platform import resolve_interpret
from .kernel import quant_matmul
from .ref import quant_matmul_ref


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def quantized_linear(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                     *, use_pallas: bool = True,
                     interpret: Optional[bool] = None,
                     bm: int = 128, bn: int = 128, bk: int = 128) -> jax.Array:
    """f32/bf16 activations x pre-quantized int8 weights -> f32.

    Activations are dynamically quantized per-row (the Chipmunk x-stream is 8-bit
    too).  Shapes: x (..., K), w_q (K, N), w_scale (N,) or scalar.
    """
    lead = x.shape[:-1]
    k, n = w_q.shape
    x2 = x.reshape(-1, k)
    xs = quant.abs_max_scale(x2, axis=-1)          # (M, 1) per-row
    x_q = quant.quantize_scaled(x2, xs)
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32), (n,))[None, :]

    if not use_pallas:
        out = quant_matmul_ref(x_q, w_q, xs, ws)
    else:
        m = x_q.shape[0]
        bm_eff = min(bm, max(8, m))
        x_p = _pad_to(_pad_to(x_q, bm_eff, 0), bk, 1)
        w_p = _pad_to(_pad_to(w_q, bk, 0), bn, 1)
        xs_p = _pad_to(xs, bm_eff, 0)
        ws_p = _pad_to(ws, bn, 1)
        out = quant_matmul(x_p, w_p, xs_p, ws_p, bm=bm_eff, bn=bn, bk=bk,
                           interpret=resolve_interpret(interpret))[:m, :n]
    return out.reshape(lead + (n,))


def quantize_weights(w: jax.Array):
    """Per-output-channel symmetric int8 weights.  w: (K, N) -> (w_q, scale (N,))."""
    scale = quant.abs_max_scale(w, axis=0)         # (1, N)
    return quant.quantize_scaled(w, scale), scale[0]
