"""Public ops for the fused whole-stack wavefront LSTM kernel.

``lstm_stack_seq`` is the stack-level drop-in for looping
``core.lstm.lstm_layer_fused`` over the layers of ``lstm_stack_apply`` /
``lstm_stack_chunk`` (the dense read-out stays at the call site): one kernel
launch executes every layer, forward allclose to the layerwise composition
and backward through the cross-layer extension of the gate-recompute VJP.
``lstm_stack_seq_quantized`` is the whole-stack form of chaining
``lstm_layer_seq_quantized`` layer by layer — bit-identical int8 hidden
codes, one launch instead of L, including the opaque per-layer ``(h_q,
c_q)`` chunk carry and the §7 valid-length mask.  Padding to MXU tiles, the
hoisted layer-0 input matmul, the ``(k, gate, n)`` weight relayout, and
un-padding all live here so call sites never see kernel geometry.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...core.lstm import (GATES, LSTMStackParams,
                          lstm_stack_bwd_recompute_gates, stack_carry_arrays,
                          valid_len_mask)
from ...core.systolic import QuantizedPackedLSTM, quantized_x_prefix
from .._padding import (lane_tile, pad_axis_to as _pad_to, pad_tiles,
                        round_up as _round_up, unpad_tiles)
from .._platform import resolve_interpret
from .ops import _dense_from_tiles
from .stack_kernel import lstm_stack_seq_kernel, lstm_stack_seq_kernel_q


def stack_vmem_bytes_estimate(n_x: int, n_h: int, n_layers: int, batch: int,
                              bn: int = 128, bk: int = 128,
                              dtype_bytes: int = 4,
                              bb: Optional[int] = None) -> int:
    """Resident VMEM working set of the fused f32 stack kernel (for selection).

    A conservative upper bound with no numerics of its own: stack-level
    backend selection admits ``pallas_seq_fused`` only when this fits the
    VMEM budget, falling back to the layerwise ``pallas_seq`` path
    otherwise.  Counts BOTH resident weight families (every layer's ``W_h``
    plus the inner layers' ``W_in``), the per-layer peephole/bias rows, the
    per-layer h/c scratch (double-buffered h), the per-layer carried
    ``h0/c0`` blocks, and the double-buffered streamed blocks.
    """
    n_h_p = _round_up(n_h, math.lcm(bn, bk))
    b_p = max(8, _round_up(batch, 8))
    b_s = b_p if bb is None else min(b_p, bb)
    weights = 2 * n_layers * GATES * n_h_p * n_h_p * dtype_bytes
    consts = n_layers * (3 + GATES) * n_h_p * dtype_bytes
    state = (n_layers * 3 * b_s * n_h_p * 4            # h (x2) + c scratch
             + 2 * n_layers * b_s * n_h_p * dtype_bytes)  # h0/c0 blocks
    stream = 2 * (GATES * b_s * bn * dtype_bytes       # pre_x block
                  + 2 * 2 * b_s * bn * dtype_bytes)    # hs/cs out blocks
    return weights + consts + state + stream


def stack_fused_compatible(params: LSTMStackParams) -> bool:
    """Structural admission for the fused stack kernel (no numerics of its
    own — pure dispatch): True iff every layer shares one hidden width and
    every inner layer's input width equals it, i.e. the stack is the
    homogeneous ``n_x -> n_h -> n_h -> ...`` shape whose inter-layer
    handover the wavefront scratch can carry.  Heterogeneous stacks fall
    back to the layerwise path."""
    layers = params.layers
    if not layers:
        return False
    n_h = layers[0].n_h
    return (all(l.n_h == n_h for l in layers)
            and all(l.n_x == n_h for l in layers[1:]))


# ---------------------------------------------------------------------------
# f32 path with the cross-layer production training VJP
# ---------------------------------------------------------------------------

def _stack_forward(cfg, w_in, w_h, peep, b, pre_x, h0s, c0s, mask=None):
    """Pad, relayout, run the wavefront kernel, un-pad.

    Numerics-neutral wrapper (zero padding + layout transposes only).
    w_in/w_h: (L, 4, N_h, N_h) core layout (``w_in[0]`` ignored); pre_x:
    (T, B, 4, N_h); h0s/c0s: (L, B, N_h); mask: optional (T, B).  Returns
    (hs, cs), each (L, T, B, N_h).
    """
    bn, bk, bb, lb, interpret = cfg
    T, B, _, n_h = pre_x.shape
    n_h_p = _round_up(n_h, math.lcm(bn, bk))
    b_p = max(8, _round_up(B, 8))
    if bb is not None:
        b_p = _round_up(b_p, bb)

    def relayout(w):  # (L, 4, N, K) -> resident (L, K, 4, N), padded
        w = _pad_to(_pad_to(w, n_h_p, 2), n_h_p, 3)
        return jnp.transpose(w, (0, 3, 1, 2))

    pre_k = _pad_to(_pad_to(pre_x, n_h_p, 3), b_p, 1)
    peep_p = _pad_to(peep, n_h_p, 2)
    bias_p = _pad_to(b, n_h_p, 2)
    h0_p = _pad_to(_pad_to(h0s, n_h_p, 2), b_p, 1)
    c0_p = _pad_to(_pad_to(c0s, n_h_p, 2), b_p, 1)
    mask_p = None if mask is None else _pad_to(
        mask.astype(pre_x.dtype), b_p, 1)

    hs_d, cs_d = lstm_stack_seq_kernel(
        pre_k, relayout(w_in), relayout(w_h), peep_p, bias_p, h0_p, c0_p,
        mask_p, bn=bn, bk=bk, bb=bb, lb=lb, interpret=interpret)
    # Diagonal-major -> layer-major: layer l's trajectory is its diagonal
    # band hs[l:l+T, l] (a pure re-indexing; bubble entries are dropped).
    L = w_h.shape[0]
    hs = jnp.stack([hs_d[l:l + T, l, :B, :n_h] for l in range(L)])
    cs = jnp.stack([cs_d[l:l + T, l, :B, :n_h] for l in range(L)])
    return hs, cs


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def lstm_stack_seq_fused(cfg, w_in, w_h, peep, b, pre_x, h0s, c0s):
    """Fused stack with the cross-layer gate-recompute VJP: forward allclose
    to looping ``core.lstm.lstm_scan_fused`` over the layers (each layer's
    output feeding the next), backward numerically equal to composing the
    per-layer ``lstm_bwd_recompute_gates`` down the stack — the inner
    layers' input-weight gradients and the handover cotangents are the only
    additions over the single-layer VJP.

    cfg is the static (bn, bk, bb, lb, interpret) tuple.  Returns (ys = top
    layer's hs (T, B, N_h), (h_T (L, B, N_h), c_T (L, B, N_h))).
    """
    hs, cs = _stack_forward(cfg, w_in, w_h, peep, b, pre_x, h0s, c0s)
    return hs[-1], (hs[:, -1], cs[:, -1])


def _stack_fwd(cfg, w_in, w_h, peep, b, pre_x, h0s, c0s):
    hs, cs = _stack_forward(cfg, w_in, w_h, peep, b, pre_x, h0s, c0s)
    return ((hs[-1], (hs[:, -1], cs[:, -1])),
            (w_in, w_h, peep, b, pre_x, hs, cs, h0s, c0s))


def _stack_bwd(cfg, res, grads):
    # Cross-layer gate recompute lives in core.lstm so the staged systolic
    # scale-out's VJP (core.systolic) composes the identical backward.
    w_in, w_h, peep, b, pre_x, hs, cs, h0s, c0s = res
    return lstm_stack_bwd_recompute_gates(w_in, w_h, peep, b, pre_x, hs, cs,
                                          h0s, c0s, grads)


lstm_stack_seq_fused.defvjp(_stack_fwd, _stack_bwd)


def _stack_arrays(params: LSTMStackParams):
    """Stack per-layer params into the (L, ...) kernel arrays (layer 0's
    input weights ride separately as the hoisted ``pre_x`` matmul)."""
    layers = params.layers
    w_h = jnp.stack([l.w_h for l in layers])
    w_in = jnp.stack([jnp.zeros_like(layers[0].w_h)]
                     + [l.w_x for l in layers[1:]])
    peep = jnp.stack([l.w_peep for l in layers])
    b = jnp.stack([l.b for l in layers])
    return w_in, w_h, peep, b


def _tuned_lb(n_x: int, n_h: int, n_layers: int, T: int,
              B: int) -> Optional[int]:
    """Tuned §8 layer-block streaming factor from the installed schedule
    cache (kind ``'stack_lb'``), or None on a miss.  Grid-only by contract
    (every legal ``lb`` is bit-equal), but the divisibility the grid needs
    is re-validated here — a stale entry can never break a launch."""
    from ...tune.schedule import current_schedule_cache
    cache = current_schedule_cache()
    if cache is None:
        return None
    ent = cache.lookup('stack_lb', n_x=n_x, n_h=n_h, n_layers=n_layers,
                       T=T, B=B)
    if ent is None or not ent.lb:
        return None
    lb = int(ent.lb)
    return lb if 1 <= lb <= n_layers and n_layers % lb == 0 else None


def lstm_stack_seq(params: LSTMStackParams, xs: jax.Array,
                   states: Optional[Sequence] = None, *,
                   valid_len: Optional[jax.Array] = None,
                   bn: Optional[int] = None, bk: Optional[int] = None,
                   bb: Optional[int] = None, lb: Optional[int] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, Tuple]:
    """Fused drop-in for the layer loop of ``core.lstm.lstm_stack_apply`` /
    ``lstm_stack_chunk`` (everything except the dense read-out): ONE
    wavefront launch for all layers, output allclose to the layerwise
    composition on any backend, differentiable via the cross-layer
    gate-recompute VJP.

    xs: (T, B, N_x); states: optional per-layer ``((h, c), ...)`` carries
    from a previous chunk.  Requires ``stack_fused_compatible(params)``
    (homogeneous hidden widths) — dispatch falls back to the layerwise path
    otherwise.  ``valid_len``: optional (B,) ragged valid lengths shared by
    every layer (DESIGN.md §7 masking contract: a masked step is identity
    on each layer's carried state; inference-only, like the layerwise
    masked paths).  ``bb``/``lb`` select the batch-block and layer-block
    grid dimensions (defaults: one block each — all serving slots share one
    weight DMA, the whole stack stays resident; with a schedule cache
    installed, a tuned ``'stack_lb'`` winner fills ``lb=None`` first —
    grid-only by the §8 contract, bit-equal across every legal ``lb``).
    Returns (hs_top (T, B, N_h), per-layer ((h_T, c_T), ...)).
    """
    assert stack_fused_compatible(params), \
        'fused stack kernel needs homogeneous hidden widths'
    layers = params.layers
    n_h = layers[0].n_h
    T, B = xs.shape[0], xs.shape[1]
    assert xs.ndim == 3, 'lstm_stack_seq expects (T, B, N_x) input'
    interpret = resolve_interpret(interpret)
    if bn is None or bk is None:
        n_h_p = _round_up(n_h, 128)
        auto = next(b for b in (512, 256, 128) if n_h_p % b == 0)
        bn = bn or auto
        bk = bk or auto
    assert bb is None or bb % 8 == 0, \
        f'bb={bb} must be a multiple of 8 (f32 sublane tiling)'

    w_in, w_h, peep, b = _stack_arrays(params)
    pre_x = jnp.einsum('ghx,tbx->tbgh', layers[0].w_x, xs)    # hoisted

    h0s, c0s = stack_carry_arrays(states, len(layers), B, n_h, xs.dtype)
    if lb is None:
        lb = _tuned_lb(layers[0].n_x, n_h, len(layers), T, B)
    assert lb is None or len(layers) % lb == 0, (len(layers), lb)
    cfg = (bn, bk, bb, lb, interpret)

    if valid_len is not None:
        mask = valid_len_mask(T, valid_len, B)
        hs, cs = _stack_forward(cfg, w_in, w_h, peep, b, pre_x, h0s, c0s,
                                mask)
        ys, h_T, c_T = hs[-1], hs[:, -1], cs[:, -1]
    else:
        ys, (h_T, c_T) = lstm_stack_seq_fused(cfg, w_in, w_h, peep, b,
                                              pre_x, h0s, c0s)
    finals = tuple((h_T[l], c_T[l]) for l in range(len(layers)))
    return ys, finals


# ---------------------------------------------------------------------------
# int8 path — whole-stack silicon datapath
# ---------------------------------------------------------------------------

def lstm_stack_seq_quantized(qps: Sequence[QuantizedPackedLSTM],
                             xs_q: jax.Array, *,
                             state: Optional[Tuple[jax.Array, jax.Array]] = None,
                             valid_len: Optional[jax.Array] = None,
                             return_state: bool = False,
                             bb: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Whole-stack int8 wavefront execution: bit-identical to chaining
    ``lstm_layer_seq_quantized`` (and hence the silicon reference scan
    ``systolic_cell_quantized``) layer by layer, with each layer's hidden
    codes fed as the next layer's input codes — one launch instead of L,
    the inter-layer codes never leaving VMEM scratch.

    qps: per-layer quantized packs sharing one ``tile`` and one hidden
    width (every inner layer's ``n_x`` == the stack's ``n_h``); xs_q:
    (T, B, n_x) int8 codes.  ``state``: opaque per-layer carry ``(h_q,
    c_q)``, each (L, B, padded_h) int8 as returned by a previous call with
    ``return_state=True`` (None = zero state); ``valid_len``: (B,) ragged
    mask shared by every layer.  Returns the top layer's (T, B, n_h) int8
    hidden codes, plus the state tuple when ``return_state``.
    """
    plans = [qp.plan for qp in qps]
    p0 = plans[0]
    L = len(qps)
    assert L >= 1
    assert all(p.tile == p0.tile for p in plans), 'mixed tiles'
    assert all(p.n_h == p0.n_h for p in plans), 'mixed hidden widths'
    assert all(p.n_x == p0.n_h for p in plans[1:]), \
        'inner layers must consume the stack hidden width'
    tile, cols_h, padded_h = p0.tile, p0.cols_h, p0.padded_h
    assert xs_q.ndim == 3, 'lstm_stack_seq_quantized expects (T, B, n_x)'
    T, B = xs_q.shape[0], xs_q.shape[1]
    b_p = B if bb is None else _round_up(B, bb)
    # Kernel layout: every engine tile zero-padded to whole 128-lane vregs
    # (``_dense_from_tiles``); the opaque state carry keeps the silicon
    # layout, so it converts on the way in and out.
    tp = lane_tile(tile)

    # Resident weight relayout: dense (4, R*tp, C*tp) per layer ->
    # (k, gate, n); inner layers fill the whole 2*cols_h*tp column span,
    # layer 0 only the own-h region (its x prefix is hoisted into acc_x).
    w_cols = 2 * cols_h * tp
    w_all = []
    peep_all, bias_all = [], []
    for l, qp in enumerate(qps):
        dense, peep, bias = _dense_from_tiles(qp)
        if l == 0:
            w_l = jnp.zeros((GATES, dense.shape[1], w_cols), jnp.int8
                            ).at[:, :, cols_h * tp:].set(
                                dense[:, :, plans[0].cols_x * tp:])
        else:
            w_l = dense                      # padded_in == 2*cols_h*tile
        w_all.append(jnp.transpose(w_l, (2, 0, 1)))
        peep_all.append(peep)
        bias_all.append(bias)
    w_all = jnp.stack(w_all)
    peep_all = jnp.stack(peep_all)
    bias_all = jnp.stack(bias_all)

    # Layer 0's x-region saturating-hop prefix, hoisted for the whole
    # sequence — the ONE shared implementation (core.systolic), so the §6
    # and §8 consumers cannot drift apart in saturation or hop order.
    xs_flat = jnp.zeros((T, b_p, p0.n_x), jnp.int8).at[:, :B].set(xs_q)
    acc_x = _pad_to(quantized_x_prefix(qps[0], xs_flat), tp, 4)
    h0 = jnp.zeros((L, b_p, padded_h), jnp.int8)
    c0 = jnp.zeros((L, b_p, padded_h), jnp.int8)
    if state is not None:
        h0 = h0.at[:, :B].set(state[0])
        c0 = c0.at[:, :B].set(state[1])
    mask = None
    if valid_len is not None:
        mask = jnp.zeros((T, b_p), jnp.int8).at[:, :B].set(
            valid_len_mask(T, valid_len, B).astype(jnp.int8))

    hs_d, cs_d = lstm_stack_seq_kernel_q(
        acc_x, w_all, peep_all, bias_all,
        qps[0].sig_lut.reshape(1, 256), qps[0].tanh_lut.reshape(1, 256),
        pad_tiles(h0, tile, 2), pad_tiles(c0, tile, 2), mask, tile=tp,
        cols_h=cols_h, bb=bb, interpret=resolve_interpret(interpret))
    hs_d, cs_d = unpad_tiles(hs_d, tile, 3), unpad_tiles(cs_d, tile, 3)
    # Diagonal-major -> layer-major, exactly as in the f32 wrapper: layer
    # l's trajectory is its diagonal band hs_d[l:l+T, l] (pure re-indexing;
    # bubble entries are dropped).
    hs = jnp.stack([hs_d[l:l + T, l] for l in range(L)])
    cs = jnp.stack([cs_d[l:l + T, l] for l in range(L)])
    out = hs[-1, :, :B, :p0.n_h]
    if not return_state:
        return out
    return out, (hs[:, -1, :B], cs[:, -1, :B])


def lstm_stack_seq_quantized_auto(qps: Sequence[QuantizedPackedLSTM],
                                  xs_q: jax.Array, *,
                                  state: Optional[Tuple[jax.Array,
                                                        jax.Array]] = None,
                                  valid_len: Optional[jax.Array] = None,
                                  return_state: bool = False,
                                  bb: Optional[int] = None,
                                  interpret: Optional[bool] = None,
                                  backend: str = 'auto'):
    """Shape-dispatched whole-stack int8 execution.

    Picks the fused wavefront (``lstm_stack_seq_quantized``) or the
    layerwise chain of ``lstm_layer_seq_quantized`` calls via
    ``core.lstm.select_quantized_stack_backend`` — since the §12 autotuner
    that decision consults the installed measured-schedule cache first
    (``repro.tune``), with the BENCH_kernels.json-calibrated width floor as
    the cold-cache fallback: the calibration pair shows the wavefront
    LOSING to the chain at small hidden widths (its fill/drain bubble and
    relayout overheads are fixed while the per-layer work shrinks), so
    small stacks run layerwise.  Bit-identical
    either way — that is the fused kernel's contract — and BOTH paths speak
    the STACK state layout (opaque ``(h_q, c_q)``, each ``(L, B, padded_h)``
    int8), so a chunked streaming caller can carry state across chunks
    regardless of which launch shape each chunk resolved to.  ``backend``
    forces ``'fused'``/``'layerwise'`` explicitly (tests pin both).
    """
    assert xs_q.ndim == 3, 'lstm_stack_seq_quantized_auto expects (T, B, n_x)'
    if backend == 'auto':
        from ...core.lstm import select_quantized_stack_backend
        backend = select_quantized_stack_backend(
            qps[0].plan.n_h, len(qps), xs_q.shape[0], xs_q.shape[1])
    assert backend in ('fused', 'layerwise'), backend
    if backend == 'fused':
        return lstm_stack_seq_quantized(
            qps, xs_q, state=state, valid_len=valid_len,
            return_state=return_state, bb=bb, interpret=interpret)
    from .ops import lstm_layer_seq_quantized
    out = xs_q
    h_fin, c_fin = [], []
    for l, qp in enumerate(qps):
        st_l = None if state is None else (state[0][l], state[1][l])
        out, (h_l, c_l) = lstm_layer_seq_quantized(
            qp, out, state=st_l, valid_len=valid_len, return_state=True,
            bb=bb, interpret=interpret)
        h_fin.append(h_l)
        c_fin.append(c_l)
    if not return_state:
        return out
    return out, (jnp.stack(h_fin), jnp.stack(c_fin))
