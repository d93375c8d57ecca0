"""Public op: fused LSTM cell / layer with padding; drop-in for core.lstm."""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.lstm import LSTMParams, lstm_bwd_recompute_gates
from .._padding import pad_axis_to, pad_axis_to_multiple, round_up
from .._platform import resolve_interpret
from .kernel import lstm_gates, lstm_gates_rec
from .ref import lstm_gates_ref


def lstm_cell_fused(params: LSTMParams, x_t: jax.Array, h_prev: jax.Array,
                    c_prev: jax.Array, *, bn: int = 128, bk: int = 128,
                    use_pallas: bool = True,
                    interpret: Optional[bool] = None):
    """Same contract as core.lstm.lstm_cell, via the fused kernel."""
    n_h, n_x = params.n_h, params.n_x
    w = jnp.concatenate([params.w_x, params.w_h], axis=-1)  # (4, N_h, N_in)
    xh = jnp.concatenate([x_t, h_prev], axis=-1)
    if not use_pallas:
        h, c = lstm_gates_ref(xh, w, params.w_peep, params.b, c_prev)
        return h, c
    b = xh.shape[0]
    b_pad = max(8, round_up(b, 8))
    xh_p = pad_axis_to(pad_axis_to_multiple(xh, bk, 1), b_pad, 0)
    w_p = pad_axis_to_multiple(pad_axis_to_multiple(w, bn, 1), bk, 2)
    peep_p = pad_axis_to_multiple(params.w_peep, bn, 1)
    bias_p = pad_axis_to_multiple(params.b, bn, 1)
    c_p = pad_axis_to(pad_axis_to_multiple(c_prev, bn, 1), b_pad, 0)
    h, c = lstm_gates(xh_p, w_p, peep_p, bias_p, c_p, bn=bn, bk=bk,
                      interpret=resolve_interpret(interpret))
    return h[:b, :n_h], c[:b, :n_h]


# ---------------------------------------------------------------------------
# Layer: per-step kernel scanned over time, with the training VJP
# ---------------------------------------------------------------------------

def _step_forward(cfg, w_h, w_peep, b, pre_x, h0, c0):
    """Pad once, scan the recurrent-only kernel.  pre_x: (T, B, 4, N_h)."""
    bn, bk, interpret = cfg
    T, B, _, n_h = pre_x.shape
    n_h_p = round_up(n_h, math.lcm(bn, bk))
    b_pad = max(8, round_up(B, 8))

    # ---- hoisted, once per layer call -------------------------------------
    w_h_p = pad_axis_to(pad_axis_to(w_h, n_h_p, 1), n_h_p, 2)
    peep_p = pad_axis_to(w_peep, n_h_p, 1)
    bias_p = pad_axis_to(b, n_h_p, 1)
    pre_p = pad_axis_to(pad_axis_to(pre_x, n_h_p, 3), b_pad, 1)
    h0_p = pad_axis_to(pad_axis_to(h0, n_h_p, 1), b_pad, 0)
    c0_p = pad_axis_to(pad_axis_to(c0, n_h_p, 1), b_pad, 0)

    def step(carry, pre_t):
        h, c = carry
        h, c = lstm_gates_rec(h, w_h_p, pre_t, peep_p, bias_p, c,
                              bn=bn, bk=bk, interpret=interpret)
        return (h, c), (h, c)

    _, (hs, cs) = jax.lax.scan(step, (h0_p, c0_p), pre_p)
    return hs[:, :B, :n_h], cs[:, :B, :n_h]


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def lstm_step_fused(cfg, w_h, w_peep, b, pre_x, h0, c0):
    """Per-step kernel layer with the shared gate-recompute VJP, so the
    ``pallas_step`` backend is trainable just like ``pallas_seq``."""
    hs, cs = _step_forward(cfg, w_h, w_peep, b, pre_x, h0, c0)
    return hs, (hs[-1], cs[-1])


def _step_fwd(cfg, w_h, w_peep, b, pre_x, h0, c0):
    hs, cs = _step_forward(cfg, w_h, w_peep, b, pre_x, h0, c0)
    return (hs, (hs[-1], cs[-1])), (w_h, w_peep, b, pre_x, hs, cs, h0, c0)


def _step_bwd(cfg, res, grads):
    w_h, w_peep, b, pre_x, hs, cs, h0, c0 = res
    return lstm_bwd_recompute_gates(w_h, w_peep, b, pre_x, hs, cs, h0, c0,
                                    grads)


lstm_step_fused.defvjp(_step_fwd, _step_bwd)


def lstm_layer_fused(params: LSTMParams, xs: jax.Array, *,
                     h0: Optional[jax.Array] = None,
                     c0: Optional[jax.Array] = None,
                     bn: int = 128, bk: int = 128, use_pallas: bool = True,
                     interpret: Optional[bool] = None,
                     return_state: bool = False):
    """Scan the fused cell over time.  xs: (T, B, N_x).

    Everything per-step-invariant is hoisted out of the scan body: weight
    padding happens once, and the non-recurrent ``W_x @ x_t`` contribution is
    one wide matmul over the whole sequence — the scan body only pays the
    recurrent ``W_h @ h`` MACs through the recurrent-only kernel
    (``lstm_gates_rec``), the same hoisting ``core.lstm.lstm_layer`` does and
    what the silicon's weight-stationary streaming implies.
    """
    n_h = params.n_h
    B = xs.shape[1]
    if h0 is None:
        h0 = jnp.zeros((B, n_h), xs.dtype)
    if c0 is None:
        c0 = jnp.zeros((B, n_h), xs.dtype)

    if not use_pallas:
        w = jnp.concatenate([params.w_x, params.w_h], axis=-1)

        def step_ref(carry, x_t):
            h, c = carry
            xh = jnp.concatenate([x_t, h], axis=-1)
            h, c = lstm_gates_ref(xh, w, params.w_peep, params.b, c)
            return (h, c), h

        (h_T, c_T), hs = jax.lax.scan(step_ref, (h0, c0), xs)
        return (hs, (h_T, c_T)) if return_state else hs

    pre_x = jnp.einsum('ghx,tbx->tbgh', params.w_x, xs)      # wide matmul
    hs, state = lstm_step_fused((bn, bk, resolve_interpret(interpret)),
                                params.w_h,
                                params.w_peep, params.b, pre_x, h0, c0)
    return (hs, state) if return_state else hs
