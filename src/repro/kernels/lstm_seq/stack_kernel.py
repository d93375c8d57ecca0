"""Pallas TPU kernel: fused whole-stack wavefront LSTM (one launch, L layers).

The single-layer persistent kernel (``kernel.py``) already keeps one layer's
weights and state VMEM-resident for the whole sequence — but a *stack* of L
layers still pays L launches, writing the full ``(T, B, N_h)`` hidden
sequence to HBM after each layer and re-reading it as the next layer's
input.  Chipmunk's systolic scale-out exists precisely to avoid that at the
stack level: columns of engine tiles hold *different layers'* weights
stationary and the hidden state hops tile-to-tile instead of round-tripping
through memory (paper Fig. 3, Sec. 3.3 — the 3x(5x5) Graves configuration).

This kernel is the TPU analogue: ONE ``pallas_call`` whose grid carries a
(blocked) layer dimension and executes the stack as a **wavefront
pipeline**,

  * grid ``(NB, D, L/lb, J, K)`` with ``D = T + L - 1`` diagonals: at
    diagonal ``d`` layer ``l`` executes its timestep ``t = d - l``, so
    layer ``l`` consumes step ``t`` while layer ``l+1`` consumes step
    ``t-1`` — the paper's tile-column layer placement as a schedule.  The
    layer dimension is blocked like every other grid dimension: all layers
    of one block execute their (mutually independent — every dependency
    points at the previous diagonal) steps as batched MXU dots in one grid
    step, which is exactly the silicon picture of all tile columns firing
    concurrently within a cycle.  The default block is the whole stack;
  * with the whole stack in one block, EVERY layer's recurrent ``W_h`` and
    (for ``l > 0``) input ``W_in`` use constant index maps — DMAed into
    VMEM once, resident for the entire sequence.  Smaller layer blocks
    (``lb < L``) degrade gracefully to partial residency: layer blocks
    re-stream once per diagonal, the schedule is unchanged;
  * inter-layer handover lives in scratch: layer ``l`` reads layer
    ``l-1``'s ``h_t`` straight out of the t-parity double buffer written one
    diagonal earlier — the hidden sequence never touches HBM between layers;
  * layer 0's non-recurrent ``W_x @ x`` stream is hoisted out of the kernel
    (exactly like the single-layer kernel); inner layers' input matmuls
    cannot be hoisted (their inputs are produced in-kernel) and run against
    the resident ``W_in`` blocks (``W_in[0]`` is zero, so the batched
    below-layer dot is a no-op contribution for layer 0);
  * the 4 gate dots fuse into ONE ``(lb, B, bk) x (lb, bk, 4*bn)`` batched
    MXU dot per resident block (weights pre-transposed to ``(L, K, 4, N)``
    layout by the ops wrapper) — one dispatch per diagonal where the
    layerwise composition pays ``4 * L`` per timestep;
  * outputs are written diagonal-major — ``hs[d, l] = layer l's step
    d - l`` — so every grid step owns a distinct output block (fill/drain
    bubbles land on diagonals outside each layer's ``[l, l + T)`` band and
    are simply never gathered); the ops wrapper re-indexes to the
    layer-major ``(L, T, B, N_h)`` view.

Masking follows the DESIGN.md §7 contract verbatim: a masked step is a pure
``jnp.where`` identity on every layer's carried state (an all-ones mask is
bit-identical to the unmasked schedule), and ``h0/c0`` per layer plus the
emitted ``cs`` make the kernel chunk-carriable for the streaming engine.

The int8 variant replays the silicon datapath of
``core.systolic.systolic_cell_quantized`` layer by layer: layer 0's x-region
saturating-hop prefix is precomputed per step (bit-identical hoisting, as in
``systolic_lstm_seq_quantized``), inner layers consume the layer-below int8
``h`` codes from scratch as their x-region columns — exactly the codes the
layerwise composition would round-trip through HBM — so the fused stack is
bit-identical to chaining the layerwise kernel.  Like the f32 kernel, each
diagonal's layers execute TOGETHER: grid ``(NB, D, R, C)`` with one L-wide
batched ``dot_general`` per hop position — different layers' hop chains are
independent, so batching across layers never reorders any single chain's
saturations, while the serial hop replay stays per-layer inside each
accumulator row — and outputs written diagonal-major exactly as in f32
(bubbles outside each layer's band flush defined data, never gathered).
Cutting the grid from ``D·L·R·C`` to ``D·R·C`` steps removes the dominant
per-grid-step cost of interpret-mode emulation (and L launches' worth of
grid sequencing on hardware).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import quant
from ...core.systolic import ACC_FMT, CELL_FMT
from .._platform import vmem_compiler_params
from .kernel import apply_lut_ref

_sat16 = quant.saturate_int16
_rshift_round = quant.rshift_round


# ---------------------------------------------------------------------------
# f32 wavefront kernel
# ---------------------------------------------------------------------------

def _stack_kernel(pre_x_ref, w_in_ref, w_h_ref, peep_ref, bias_ref, h0_ref,
                  c0_ref, mask_ref, hs_ref, cs_ref, h_scr, c_scr, acc_ref, *,
                  T: int, L: int, lb: int, n_k: int, bn: int, bk: int):
    # Grid (NB, D, L/lb, J, K): batch blocks outermost (one weight DMA serves
    # all serving slots), then the wavefront diagonal, the layer blocks, the
    # output-row blocks and the reduction blocks.
    d = pl.program_id(1)
    m = pl.program_id(2)
    j = pl.program_id(3)
    k = pl.program_id(4)
    base = m * lb                      # first layer of this layer block

    @pl.when((d == 0) & (m == 0) & (j == 0) & (k == 0))
    def _load_state():
        # Both parity slots start defined (the below-layer batched dot reads
        # the off-parity slot of layer l-1 before it is first written; its
        # contribution is zeroed by w_in[0]=0 / discarded by the wavefront
        # select, but the read must not touch undefined memory).
        h_scr[:, 0] = h0_ref[...].astype(jnp.float32)
        h_scr[:, 1] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The lb layers of this block run their diagonal steps TOGETHER: layer
    # base+i is at t = d - (base+i), and every operand it needs was written
    # on diagonal d-1 (its own h_{t-1} and the layer below's h_t), so the
    # steps are mutually independent — one batched MXU pass, the in-kernel
    # image of all tile columns firing concurrently (paper Fig. 3).
    ksl = pl.ds(k * bk, bk)
    own = jnp.stack(
        [h_scr[base + i, (d - (base + i)) % 2, :, ksl] for i in range(lb)])
    below = jnp.stack(
        [h_scr[jnp.maximum(base + i - 1, 0), (d - (base + i) + 1) % 2,
               :, ksl] for i in range(lb)])
    jsl = pl.ds(j * bn, bn)
    w_own = w_h_ref[:, ksl, :, jsl].reshape(lb, bk, 4 * bn)
    w_below = w_in_ref[:, ksl, :, jsl].reshape(lb, bk, 4 * bn)
    bdot = lambda x, w: jax.lax.dot_general(
        x, w, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    acc_ref[...] += (bdot(own, w_own)
                     + bdot(below, w_below)).reshape(*own.shape[:2], 4, bn)

    @pl.when(k == n_k - 1)
    def _elementwise():
        sl = pl.ds(j * bn, bn)
        pre_all = acc_ref[...]                                   # (lb,B,4,bn)
        for i in range(lb):
            l = base + i
            t = d - l
            tc = jnp.clip(t, 0, T - 1)
            pre = pre_all[i]
            if i == 0:
                # Layer 0's hoisted W_x @ x stream joins its block here.
                pre = pre + jnp.where(m == 0,
                                      pre_x_ref[0].astype(jnp.float32), 0.0)
            peep = peep_ref[i, :, sl].astype(jnp.float32)        # (3, bn)
            bias = bias_ref[i, :, sl].astype(jnp.float32)        # (4, bn)
            c_prev = c_scr[l, :, sl]                             # (B, bn)
            ig = jax.nn.sigmoid(pre[:, 0] + peep[0] * c_prev + bias[0])
            fg = jax.nn.sigmoid(pre[:, 1] + peep[1] * c_prev + bias[1])
            gg = jnp.tanh(pre[:, 2] + bias[2])
            c_new = fg * c_prev + ig * gg
            og = jax.nn.sigmoid(pre[:, 3] + peep[2] * c_new + bias[3])
            h_new = og * jnp.tanh(c_new)
            # Selects cover the §7 masking contract AND the wavefront
            # fill/drain bubbles: a masked or off-wavefront step is a pure
            # identity on the resident state (no arithmetic touches the
            # carried values, so an all-ones mask is bit-identical to the
            # unmasked schedule, and bubble output blocks — diagonals
            # outside [l, l+T), which the ops wrapper never gathers — still
            # flush defined data).  The keep value differs: a masked LIVE
            # step re-emits the carried h_{t-1} (slot t%2); a bubble must be
            # identity on its WRITE slot ((tc+1)%2) — a tail bubble that
            # copied slot t%2 instead would clobber h_{T-1}, which the layer
            # above still reads on this very diagonal when layer blocks run
            # in separate grid steps (lb < L).
            act = (t >= 0) & (t < T)
            keep = jnp.where(act, h_scr[l, tc % 2, :, sl],
                             h_scr[l, (tc + 1) % 2, :, sl])
            # The (B, 1) mask column is broadcast while still numeric
            # (Mosaic cannot relayout a boolean vector into a column).
            live = act & (jnp.broadcast_to(mask_ref[tc], h_new.shape) > 0)
            h_out = jnp.where(live, h_new, keep)
            c_out = jnp.where(live, c_new, c_prev)
            h_scr[l, (tc + 1) % 2, :, sl] = h_out
            c_scr[l, :, sl] = c_out
            hs_ref[0, i] = h_out.astype(hs_ref.dtype)
            cs_ref[0, i] = c_out.astype(cs_ref.dtype)


@functools.partial(jax.jit, static_argnames=('bn', 'bk', 'bb', 'lb',
                                             'interpret'))
def lstm_stack_seq_kernel(pre_x: jax.Array, w_in: jax.Array, w_h: jax.Array,
                          peep: jax.Array, bias: jax.Array, h0: jax.Array,
                          c0: jax.Array, mask: Optional[jax.Array] = None, *,
                          bn: int = 128, bk: int = 128,
                          bb: Optional[int] = None, lb: Optional[int] = None,
                          interpret: bool = False):
    """Whole-stack fused wavefront LSTM (raw kernel entry; padded shapes).

    pre_x: (T, B, 4, N_h) hoisted layer-0 ``W_x @ x`` pre-activations;
    w_in / w_h: (L, N_h, 4, N_h) resident blocks in ``(k, gate, n)`` layout
    (``w_in[0]`` must be ZERO — layer 0's input stream is ``pre_x``, and the
    zero block is what makes the batched below-layer dot a no-op for it);
    peep: (L, 3, N_h); bias: (L, 4, N_h); h0, c0: (L, B, N_h) per-layer
    carries; ``mask``: optional (T, B) validity mask shared by all layers
    (>0 = live; a masked step is identity on every layer's carried state,
    and ``None`` is bit-identical to an all-ones mask).  N_h must be a
    multiple of bn and bk; B a multiple of 8 and of ``bb``; L a multiple of
    the layer block ``lb`` (default: one block = the whole stack resident;
    ``lb < L`` re-streams layer blocks once per diagonal).

    Returns (hs, cs) in DIAGONAL-major layout, each (D, L, B, N_h) with
    ``D = T + L - 1``: ``hs[d, l]`` is layer ``l``'s step ``d - l``; entries
    outside each layer's ``[l, l + T)`` diagonal band are don't-care bubble
    flushes.  The ops wrapper gathers the layer-major ``(L, T, B, N_h)``
    view (layer ``L-1``'s band is the stack output; the full trajectories
    feed the cross-layer gate-recompute VJP and the chunked carry).
    """
    T, b, _, n_h = pre_x.shape
    L = w_h.shape[0]
    bb = b if bb is None else bb
    lb = L if lb is None else lb
    assert n_h % bn == 0 and n_h % bk == 0, (n_h, bn, bk)
    assert b % bb == 0, (b, bb)
    assert L % lb == 0, (L, lb)
    if mask is None:
        mask = jnp.ones((T, b), pre_x.dtype)
    # (T, B, 1) so the block's last dim equals the array's (TPU block rule)
    mask = mask.astype(jnp.float32).reshape(T, b, 1)
    n_k = n_h // bk
    D = T + L - 1

    hs, cs = pl.pallas_call(
        functools.partial(_stack_kernel, T=T, L=L, lb=lb, n_k=n_k, bn=bn,
                          bk=bk),
        grid=(b // bb, D, L // lb, n_h // bn, n_k),
        in_specs=[
            pl.BlockSpec((1, bb, 4, bn),
                         lambda nb, d, m, j, k: (jnp.clip(d, 0, T - 1),
                                                 nb, 0, j)),
            # Layer-block index maps: with lb == L these are constant, so
            # the whole stack's weights are fetched once and stay resident
            # for the entire grid.
            pl.BlockSpec((lb, n_h, 4, n_h), lambda nb, d, m, j, k: (m, 0, 0, 0)),
            pl.BlockSpec((lb, n_h, 4, n_h), lambda nb, d, m, j, k: (m, 0, 0, 0)),
            pl.BlockSpec((lb, 3, n_h), lambda nb, d, m, j, k: (m, 0, 0)),
            pl.BlockSpec((lb, 4, n_h), lambda nb, d, m, j, k: (m, 0, 0)),
            pl.BlockSpec((L, bb, n_h), lambda nb, d, m, j, k: (0, nb, 0)),
            pl.BlockSpec((L, bb, n_h), lambda nb, d, m, j, k: (0, nb, 0)),
            pl.BlockSpec((T, bb, 1), lambda nb, d, m, j, k: (0, nb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, lb, bb, bn), lambda nb, d, m, j, k: (d, m, nb, j)),
            pl.BlockSpec((1, lb, bb, bn), lambda nb, d, m, j, k: (d, m, nb, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((D, L, b, n_h), pre_x.dtype),
            jax.ShapeDtypeStruct((D, L, b, n_h), pre_x.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((L, 2, bb, n_h), jnp.float32),  # h double buffers
            pltpu.VMEM((L, bb, n_h), jnp.float32),     # c, updated in place
            pltpu.VMEM((lb, bb, 4, bn), jnp.float32),  # gate accumulator
        ],
        # both weight families of one layer block stay resident (with
        # lb == L the whole stack: 24 MiB for 3x421 in f32, over Mosaic's
        # 16 MiB default scoped limit)
        compiler_params=vmem_compiler_params(
            2 * lb * w_h[0].size * w_h.dtype.itemsize + 3 * L * bb * n_h * 4),
        interpret=interpret,
    )(pre_x, w_in, w_h, peep, bias, h0, c0, mask)
    return hs, cs


# ---------------------------------------------------------------------------
# int8 wavefront kernel — bit-accurate systolic datapath across the stack
# ---------------------------------------------------------------------------

def _stack_kernel_q(accx_ref, w_ref, peep_ref, bias_ref, sig_ref, tanh_ref,
                    h0_ref, c0_ref, mask_ref, hs_ref, cs_ref, h_scr, c_scr,
                    acc_ref, *, T: int, L: int, cols_h: int, tile: int):
    # Grid (NB, D, R, C): wavefront diagonals with EVERY layer batched per
    # grid step, as in the f32 kernel — R row blocks, C = 2*cols_h column
    # hops (below-h region then own-h region; layer 0's x-region prefix is
    # hoisted into accx and its below-region weight columns are zero, so
    # those hops are exact no-ops on its accumulator row).  The saturating
    # hop chains of different layers are independent, so the L-wide batched
    # MAC never reorders any single chain's saturations.
    d = pl.program_id(1)
    r = pl.program_id(2)
    c = pl.program_id(3)
    n_c = 2 * cols_h

    @pl.when((d == 0) & (r == 0) & (c == 0))
    def _load_state():
        # Both parity slots start defined (the below-layer column read
        # touches the off-parity slot of layer l-1 before it is first
        # written; bubbles discard the value, but the read must not touch
        # undefined memory).
        h_scr[:, 0] = h0_ref[...]
        h_scr[:, 1] = h0_ref[...]
        c_scr[...] = c0_ref[...]

    @pl.when(c == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c == cols_h)
    def _load_x_prefix():
        # Layer 0 resumes the saturating hop chain from the precomputed
        # x-region prefix (bit-identical hoisting, as in the §6 scale-out);
        # its below-region hops left the row at exactly zero.
        acc_ref[0] = accx_ref[0, :, 0]

    # Batched tile MAC: stack every layer's column input for this hop
    # position — below-h region columns read the layer below's h_t codes
    # (the chip's inter-column handover), own-h region columns this layer's
    # resident h_{t-1} — then ONE L-wide dot_general in int32 (exact),
    # saturated to the 16-bit value an engine hands to its row neighbour,
    # then the hop.
    off_b = jnp.clip(c, 0, cols_h - 1) * tile
    off_o = jnp.clip(c - cols_h, 0, cols_h - 1) * tile
    is_below = c < cols_h
    cols = []
    for l in range(L):
        tc = jnp.clip(d - l, 0, T - 1)
        below_col = h_scr[max(l - 1, 0), (tc + 1) % 2, :, pl.ds(off_b, tile)]
        own_col = h_scr[l, tc % 2, :, pl.ds(off_o, tile)]
        cols.append(jnp.where(is_below, below_col, own_col))
    col_in = jnp.stack(cols)                                 # (L, bb, tile)
    w_blk = w_ref[:, pl.ds(c * tile, tile), :, pl.ds(r * tile, tile)]
    partial = _sat16(jax.lax.dot_general(        # int8 MXU, exact int32
        col_in, w_blk.reshape(L, tile, 4 * tile),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.int32,
    ).reshape(L, col_in.shape[1], 4, tile))
    acc_ref[...] = _sat16(acc_ref[...] + partial)

    @pl.when(c == n_c - 1)
    def _elementwise():
        sl = pl.ds(r * tile, tile)
        shift8 = ACC_FMT.frac_bits - quant.STATE_FMT.frac_bits
        for l in range(L):
            t = d - l
            act = (t >= 0) & (t < T)
            tc = jnp.clip(t, 0, T - 1)
            c_prev32 = c_scr[l, :, sl].astype(jnp.int32)
            peep32 = peep_ref[l, :, sl].astype(jnp.int32)
            bias32 = bias_ref[l, :, sl].astype(jnp.int32)
            acc_l = acc_ref[l]

            def gate(idx, peep_idx, c_term, lut_ref):
                a = acc_l[:, idx, :] + bias32[idx]
                if peep_idx is not None:
                    a = a + peep32[peep_idx] * c_term
                a = _sat16(a)
                a8 = jnp.clip(_rshift_round(a, shift8), -128, 127)
                return apply_lut_ref(lut_ref, a8)

            i = gate(0, 0, c_prev32, sig_ref)
            f = gate(1, 1, c_prev32, sig_ref)
            g = gate(2, None, None, tanh_ref)
            fc = f * c_prev32                    # Q0.7 * Q2.5 -> frac 12
            ig = _rshift_round(i * g, 2)         # frac 14 -> 12
            c_new = _sat16(fc + ig)              # Q3.12
            c_new8 = jnp.clip(
                _rshift_round(c_new,
                              CELL_FMT.frac_bits - quant.STATE_FMT.frac_bits),
                -128, 127)
            o = gate(3, 2, c_new8, sig_ref)
            tanh_c = apply_lut_ref(tanh_ref, c_new8)
            h_new = _rshift_round(o * tanh_c, 14 - quant.STATE_FMT.frac_bits)
            h_new8 = jnp.clip(h_new, -128, 127).astype(jnp.int8)

            # Masked step / wavefront bubble = identity on the resident
            # codes (pure select), with the same write-slot discipline as
            # the f32 kernel: a masked LIVE step re-emits the carried
            # h_{t-1} (slot t%2), a bubble is identity on its WRITE slot.
            live = act & (jnp.broadcast_to(mask_ref[tc], h_new8.shape) > 0)
            keep_h = jnp.where(act, h_scr[l, tc % 2, :, sl],
                               h_scr[l, (tc + 1) % 2, :, sl])
            h8 = jnp.where(live, h_new8, keep_h)
            c8 = jnp.where(live, c_new8.astype(jnp.int8), c_scr[l, :, sl])
            h_scr[l, (tc + 1) % 2, :, sl] = h8
            c_scr[l, :, sl] = c8
            hs_ref[0, l] = h8
            cs_ref[0, l] = c8


@functools.partial(jax.jit, static_argnames=('tile', 'cols_h', 'bb',
                                             'interpret'))
def lstm_stack_seq_kernel_q(acc_x: jax.Array, w: jax.Array, peep: jax.Array,
                            bias: jax.Array, sig_lut: jax.Array,
                            tanh_lut: jax.Array, h0: jax.Array,
                            c0: jax.Array, mask: Optional[jax.Array] = None,
                            *, tile: int, cols_h: int,
                            bb: Optional[int] = None,
                            interpret: bool = False):
    """Whole-stack bit-accurate int8 wavefront LSTM (raw kernel entry).

    acc_x: (T, B, R, 4, tile) int32 hoisted layer-0 x-region hop prefix (the
    first ``cols_x`` saturating hops, which depend only on the frame codes);
    w: (L, 2*cols_h*tile, 4, padded_h) int8 resident blocks in ``(k, gate,
    n)`` layout — columns ``[0, cols_h*tile)`` hold each inner layer's
    input-region tiles (zero for layer 0), columns ``[cols_h*tile, ...)``
    the own-h-region tiles; peep: (L, 3, padded_h) int8; bias: (L, 4,
    padded_h) int16 in ACC_FMT; sig/tanh LUTs (1, 256) int8; h0, c0: (L, B,
    padded_h) int8 carried codes; ``mask``: optional (T, B) int8 validity
    mask shared by all layers (a masked step carries every layer's codes
    through unchanged; ``None`` is bit-identical to all-ones).

    Returns (hs, cs) in DIAGONAL-major layout like the f32 kernel, each
    (D, L, B, padded_h) int8 with ``D = T + L - 1``: ``hs[d, l]`` is layer
    ``l``'s step ``d - l``; entries outside each layer's ``[l, l + T)``
    band are don't-care bubble flushes.  After the ops wrapper's
    re-indexing the codes are bit-identical, layer by layer, to chaining
    ``kernel.lstm_seq_quantized`` with each layer's hidden codes fed as
    the next layer's input codes.
    """
    T, b = acc_x.shape[0], acc_x.shape[1]
    L = w.shape[0]
    padded_h = w.shape[3]
    bb = b if bb is None else bb
    assert b % bb == 0, (b, bb)
    assert w.shape[1] == 2 * cols_h * tile, (w.shape, cols_h, tile)
    if mask is None:
        mask = jnp.ones((T, b), jnp.int8)
    mask = mask.astype(jnp.int32).reshape(T, b, 1)   # (T, bb, 1) blocks
    R = padded_h // tile
    D = T + L - 1

    return pl.pallas_call(
        functools.partial(_stack_kernel_q, T=T, L=L, cols_h=cols_h,
                          tile=tile),
        grid=(b // bb, D, R, 2 * cols_h),
        in_specs=[
            pl.BlockSpec((1, bb, 1, 4, tile),
                         lambda nb, d, r, c: (jnp.clip(d, 0, T - 1),
                                              nb, r, 0, 0)),
            pl.BlockSpec((L, 2 * cols_h * tile, 4, padded_h),
                         lambda nb, d, r, c: (0, 0, 0, 0)),
            pl.BlockSpec((L, 3, padded_h), lambda nb, d, r, c: (0, 0, 0)),
            pl.BlockSpec((L, 4, padded_h), lambda nb, d, r, c: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # sigmoid LUT
            pl.BlockSpec(memory_space=pltpu.SMEM),   # tanh LUT
            pl.BlockSpec((L, bb, padded_h), lambda nb, d, r, c: (0, nb, 0)),
            pl.BlockSpec((L, bb, padded_h), lambda nb, d, r, c: (0, nb, 0)),
            pl.BlockSpec((T, bb, 1), lambda nb, d, r, c: (0, nb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, bb, tile),
                         lambda nb, d, r, c: (d, 0, nb, r)),
            pl.BlockSpec((1, L, bb, tile),
                         lambda nb, d, r, c: (d, 0, nb, r)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((D, L, b, padded_h), jnp.int8),
            jax.ShapeDtypeStruct((D, L, b, padded_h), jnp.int8),
        ],
        scratch_shapes=[
            pltpu.VMEM((L, 2, bb, padded_h), jnp.int8),  # h codes, t parity
            pltpu.VMEM((L, bb, padded_h), jnp.int8),     # c codes
            pltpu.VMEM((L, bb, 4, tile), jnp.int32),     # saturating accs
        ],
        compiler_params=vmem_compiler_params(w.size + 3 * L * bb * padded_h),
        interpret=interpret,
    )(acc_x, w, peep, bias, sig_lut.astype(jnp.int32),
      tanh_lut.astype(jnp.int32), h0, c0, mask)
