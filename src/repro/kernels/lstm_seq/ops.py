"""Public ops for the persistent whole-sequence LSTM kernel.

``lstm_layer_seq`` is a drop-in for ``core.lstm.lstm_layer`` (same contract,
same custom-VJP training semantics as ``lstm_layer_fused``): padding to MXU
tiles, the hoisted ``W_x @ x`` matmul, and un-padding all live here so call
sites never see kernel geometry.  ``lstm_layer_seq_quantized`` is the
whole-sequence form of ``core.systolic.systolic_layer_quantized`` —
bit-identical output, one kernel launch instead of T.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.lstm import (GATES, LSTMParams, lstm_bwd_recompute_gates,
                          valid_len_mask)
from ...core.systolic import QuantizedPackedLSTM
from .._padding import (lane_tile, pad_axis_to as _pad_to, pad_tiles,
                        round_up as _round_up, unpad_tiles)
from .._platform import resolve_interpret
from .kernel import lstm_seq, lstm_seq_quantized


def vmem_bytes_estimate(n_h: int, batch: int, bn: int = 128,
                        bk: int = 128, dtype_bytes: int = 4,
                        bb: Optional[int] = None) -> int:
    """Resident VMEM working set of the f32 sequence kernel (for selection).

    A conservative upper bound (no numerics of its own): backend selection
    admits ``pallas_seq`` only when this estimate fits the VMEM budget, so
    auto-chosen blockings never exceed what the kernel actually allocates.
    ``bb`` models the batch-block grid dimension — scratch scales with the
    block, not the full batch.
    """
    n_h_p = _round_up(n_h, math.lcm(bn, bk))
    b_p = max(8, _round_up(batch, 8))
    b_s = b_p if bb is None else min(b_p, bb)       # scratch batch rows
    weights = GATES * n_h_p * n_h_p * dtype_bytes
    consts = (3 + GATES) * n_h_p * dtype_bytes
    state = 3 * b_s * n_h_p * 4 + 2 * b_s * n_h_p * dtype_bytes  # scratch + h0/c0
    stream = 2 * (GATES * b_s * bn * dtype_bytes + 2 * b_s * bn * dtype_bytes)
    return weights + consts + state + stream


# ---------------------------------------------------------------------------
# f32 path with the production training VJP
# ---------------------------------------------------------------------------

def _seq_forward(cfg, w_h, w_peep, b, pre_x, h0, c0, mask=None):
    """Pad, run the kernel, un-pad.  pre_x: (T, B, 4, N_h) core layout.

    Numerics-neutral wrapper: zero padding + layout transposes only, so the
    kernel output (un-padded) stays allclose to ``core.lstm.lstm_layer``.
    ``mask``: optional (T, B) validity mask; padded batch rows are masked out
    (zero), so they never leave the zero state.
    """
    bn, bk, bb, interpret = cfg
    T, B, _, n_h = pre_x.shape
    n_h_p = _round_up(n_h, math.lcm(bn, bk))
    b_p = max(8, _round_up(B, 8))
    if bb is not None:
        b_p = _round_up(b_p, bb)

    pre_k = jnp.transpose(pre_x, (0, 2, 1, 3))            # (T, 4, B, N_h)
    pre_k = _pad_to(_pad_to(pre_k, n_h_p, 3), b_p, 2)
    w_p = _pad_to(_pad_to(w_h, n_h_p, 1), n_h_p, 2)
    peep_p = _pad_to(w_peep, n_h_p, 1)
    bias_p = _pad_to(b, n_h_p, 1)
    h0_p = _pad_to(_pad_to(h0, n_h_p, 1), b_p, 0)
    c0_p = _pad_to(_pad_to(c0, n_h_p, 1), b_p, 0)
    mask_p = None if mask is None else _pad_to(
        mask.astype(pre_x.dtype), b_p, 1)

    hs, cs = lstm_seq(pre_k, w_p, peep_p, bias_p, h0_p, c0_p, mask_p,
                      bn=bn, bk=bk, bb=bb, interpret=interpret)
    return hs[:, :B, :n_h], cs[:, :B, :n_h]


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def lstm_seq_fused(cfg, w_h, w_peep, b, pre_x, h0, c0):
    """Same contract as ``core.lstm.lstm_scan_fused`` but one kernel launch:
    forward allclose to the scan, backward (gate recompute from the saved h/c
    trajectories) numerically equal to the hand-written scan VJP.

    cfg is the static (bn, bk, bb, interpret) tuple; pre_x: (T, B, 4, N_h).
    """
    hs, cs = _seq_forward(cfg, w_h, w_peep, b, pre_x, h0, c0)
    return hs, (hs[-1], cs[-1])


def _seq_fwd(cfg, w_h, w_peep, b, pre_x, h0, c0):
    hs, cs = _seq_forward(cfg, w_h, w_peep, b, pre_x, h0, c0)
    return (hs, (hs[-1], cs[-1])), (w_h, w_peep, b, pre_x, hs, cs, h0, c0)


def _seq_bwd(cfg, res, grads):
    w_h, w_peep, b, pre_x, hs, cs, h0, c0 = res
    return lstm_bwd_recompute_gates(w_h, w_peep, b, pre_x, hs, cs, h0, c0,
                                    grads)


lstm_seq_fused.defvjp(_seq_fwd, _seq_bwd)


def lstm_layer_seq(params: LSTMParams, xs: jax.Array,
                   h0: Optional[jax.Array] = None,
                   c0: Optional[jax.Array] = None, *,
                   valid_len: Optional[jax.Array] = None,
                   bn: Optional[int] = None, bk: Optional[int] = None,
                   bb: Optional[int] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Drop-in for ``core.lstm.lstm_layer`` via the whole-sequence kernel:
    output allclose to the scan reference (same recurrence, one launch).

    xs: (T, B, N_x) -> (hs (T, B, N_h), (h_T, c_T)).  Differentiable (the VJP
    recomputes gates from the saved h/c trajectories).  ``bb`` selects the
    batch-block grid dimension (serving slots amortising weight residency);
    the padded batch is rounded up to a whole number of blocks.

    ``valid_len``: optional (B,) int32 per-stream valid lengths for ragged
    chunked serving — steps ``t >= valid_len[b]`` are identity on the state
    (DESIGN.md §7 masking contract), so ``(h_T, c_T)`` is the state after
    exactly ``valid_len[b]`` steps.  The masked path is inference-only (no
    custom VJP); training always runs the unmasked whole-sequence form.

    Default blocking is shape-aware: when the padded hidden row fits a single
    block (N_h <= 512) the whole row is one grid step — the weights are
    resident either way, and fewer grid steps means less per-step machinery.
    """
    assert bb is None or bb % 8 == 0, \
        f'bb={bb} must be a multiple of 8 (f32 sublane tiling)'
    interpret = resolve_interpret(interpret)
    if bn is None or bk is None:
        # Largest block that divides the 128-padded width, so auto blocking
        # never pads beyond what vmem_bytes_estimate (the backend-selection
        # admission test) assumed.
        n_h_p = _round_up(params.n_h, 128)
        auto = next(b for b in (512, 256, 128) if n_h_p % b == 0)
        bn = bn or auto
        bk = bk or auto
    n_h = params.n_h
    T = xs.shape[0]
    batch_shape = xs.shape[1:-1]
    B = int(math.prod(batch_shape)) if batch_shape else 1
    if h0 is None:
        h0 = jnp.zeros(batch_shape + (n_h,), xs.dtype)
    if c0 is None:
        c0 = jnp.zeros(batch_shape + (n_h,), xs.dtype)
    xs_flat = xs.reshape(T, B, params.n_x)
    pre_x = jnp.einsum('ghx,tbx->tbgh', params.w_x, xs_flat)  # hoisted matmul
    cfg = (bn, bk, bb, interpret)
    if valid_len is not None:
        mask = valid_len_mask(T, valid_len, B)
        hs, cs = _seq_forward(cfg, params.w_h, params.w_peep, params.b,
                              pre_x, h0.reshape(B, n_h), c0.reshape(B, n_h),
                              mask)
        h_T, c_T = hs[-1], cs[-1]
    else:
        hs, (h_T, c_T) = lstm_seq_fused(
            cfg, params.w_h, params.w_peep, params.b,
            pre_x, h0.reshape(B, n_h), c0.reshape(B, n_h))
    hs = hs.reshape((T,) + batch_shape + (n_h,))
    return hs, (h_T.reshape(batch_shape + (n_h,)),
                c_T.reshape(batch_shape + (n_h,)))


# ---------------------------------------------------------------------------
# int8 path — whole-sequence systolic datapath
# ---------------------------------------------------------------------------

def _dense_from_tiles(qp: QuantizedPackedLSTM):
    """(R, C, 4, t, t) engine tiles -> dense (4, R*tp, C*tp) VMEM layout,
    each tile zero-padded to ``tp = lane_tile(t)`` lanes (the chip's 96-wide
    tile is not a whole number of 128-lane vregs, which the TPU block rule
    needs).

    Pure relayout of the already-quantized codes (no re-rounding; padded
    weights are zero), so the kernel consuming it sees bit-for-bit the same
    weights as the tiled scan.
    """
    r, c, g, t, _ = qp.tiles_q.shape
    tp = lane_tile(t)
    tiles = _pad_to(_pad_to(qp.tiles_q, tp, 3), tp, 4)
    w = jnp.transpose(tiles, (2, 0, 3, 1, 4)).reshape(g, r * tp, c * tp)

    def rows(v):  # (R, k, t) per-row-block vectors -> (k, R*tp)
        return jnp.transpose(_pad_to(v, tp, 2), (1, 0, 2)).reshape(-1, r * tp)

    return w, rows(qp.peep_q), rows(qp.bias_q)


def lstm_layer_seq_quantized(qp: QuantizedPackedLSTM, xs_q: jax.Array, *,
                             state: Optional[Tuple[jax.Array, jax.Array]] = None,
                             valid_len: Optional[jax.Array] = None,
                             return_state: bool = False,
                             bb: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Whole-sequence form of ``systolic_layer_quantized``: bit-identical int8
    hidden codes, one kernel launch instead of T.

    xs_q: (T, ..., n_x) int8 codes -> (T, ..., n_h) int8 hidden codes.  ``bb``
    selects the batch-block grid dimension (the batch is zero-padded to a
    whole number of blocks; padded rows carry zero codes and are dropped, so
    bit-identity is unaffected).

    Chunked streaming (DESIGN.md §7): ``state`` is an opaque carry of
    ``(h_q, c_q)`` padded-layout int8 codes as returned by a previous call
    with ``return_state=True`` (None = zero state); ``valid_len`` masks
    ragged tail steps per stream (identity on the carried codes), so feeding
    a sequence chunk by chunk is bit-identical to the monolithic call.  With
    ``return_state=True`` returns ``(hs, (h_q, c_q))``.
    """
    plan = qp.plan
    batch_shape = xs_q.shape[1:-1]
    T = xs_q.shape[0]
    b = int(math.prod(batch_shape)) if batch_shape else 1
    b_p = b if bb is None else _round_up(b, bb)
    xs_flat = xs_q.reshape(T, b, plan.n_x)
    xs_pad = jnp.zeros((T, b_p, plan.padded_x), jnp.int8
                       ).at[:, :b, :plan.n_x].set(xs_flat)
    h0_q = c0_q = mask = None
    t = plan.tile

    def carry_in(codes):  # opaque (.., padded_h) carry -> kernel layout
        z = jnp.zeros((b_p, plan.padded_h), jnp.int8)
        return pad_tiles(z.at[:b].set(codes.reshape(b, plan.padded_h)), t, 1)

    if state is not None:
        h0_q, c0_q = map(carry_in, state)
    if valid_len is not None:
        mask = jnp.zeros((T, b_p), jnp.int8).at[:, :b].set(
            valid_len_mask(T, valid_len, b).astype(jnp.int8))
    w_q, peep_q, bias_q = _dense_from_tiles(qp)
    hs, cs = lstm_seq_quantized(
        pad_tiles(xs_pad, t, 2), w_q, peep_q, bias_q,
        qp.sig_lut.reshape(1, 256), qp.tanh_lut.reshape(1, 256),
        h0_q, c0_q, mask,
        tile=lane_tile(t), cols_x=plan.cols_x, bb=bb,
        interpret=resolve_interpret(interpret))
    hs, cs = unpad_tiles(hs, t, 2), unpad_tiles(cs, t, 2)
    out = hs[:, :b, :plan.n_h].reshape((T,) + batch_shape + (plan.n_h,))
    if not return_state:
        return out
    final = (hs[-1, :b].reshape(batch_shape + (plan.padded_h,)),
             cs[-1, :b].reshape(batch_shape + (plan.padded_h,)))
    return out, final
