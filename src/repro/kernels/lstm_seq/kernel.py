"""Pallas TPU kernel: persistent whole-sequence LSTM (weight-stationary).

This is the TPU analogue of Chipmunk's core loop (Sec. 3.2): the packed gate
matrix stays resident in engine SRAM for the *entire* utterance and the
``h``/``c`` state never leaves the local register file between timesteps.  The
per-step kernel in ``kernels/lstm_gates`` re-streams ``W`` from HBM and
round-trips ``h``/``c`` through HBM on every timestep; here one ``pallas_call``
owns the whole sequence:

  * grid ``(T, N_h/bn, N_h/bk)`` — time outermost, then the output-row blocks,
    then the recurrent reduction blocks;
  * the recurrent weight ``W_h`` (4, N_h, N_h), peepholes, and biases use
    constant index maps, so Mosaic DMAs them into VMEM once and every grid step
    revisits the same resident copy (weight-stationary block residency);
  * ``h``/``c`` live in VMEM scratch across all T steps.  ``h`` is
    double-buffered on t-parity because step t+1's reduction reads *all* of
    ``h_t`` while step t is still writing it block by block; ``c`` is updated
    in place (block j of ``c_t`` depends only on block j of ``c_{t-1}``);
  * the non-recurrent contribution ``W_x @ x_t`` is hoisted out of the
    recurrence into one wide matmul (exactly like ``core.lstm.lstm_layer``)
    and streamed into the kernel per (t, j) block;
  * the elementwise phase (peepholes, nonlinearities, state update) fuses into
    the final K step, so gate pre-activations never touch HBM.

Both kernels take an optional batch-block size ``bb`` that adds an OUTERMOST
batch grid dimension: each block replays the full T-step recurrence against
the same resident weights, so a serving slot grid amortises a single weight
DMA across all slots instead of paying one per batch block.

Both kernels also take a per-(t, b) validity mask (the streaming-serving
contract of DESIGN.md §7): a masked step is an *identity* on the resident
state — ``h_t = h_{t-1}``, ``c_t = c_{t-1}`` via ``jnp.where`` (no arithmetic
on the carried values, so an all-ones mask is bit-identical to the unmasked
kernel) — which is what lets ragged streams share one batched launch without
padded tail steps corrupting the state carried into the next chunk.

The int8 variant (`lstm_seq_quantized`) runs the same persistent schedule over
the bit-accurate systolic datapath of ``core.systolic.systolic_cell_quantized``:
int8 weight tiles resident in VMEM, per-tile int32 MACs saturated to int16, a
sequential saturating hop over the column blocks (x-region columns streamed,
h-region columns read from the VMEM state), LUT nonlinearities, and the exact
shift/clip alignment of the silicon.
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


# ---------------------------------------------------------------------------
# f32 kernel
# ---------------------------------------------------------------------------

def _seq_kernel(pre_x_ref, w_ref, peep_ref, bias_ref, h0_ref, c0_ref,
                mask_ref, hs_ref, cs_ref, h_scr, c_scr, acc_ref, *, n_k: int,
                bn: int, bk: int):
    # Grid (NB, T, J, K): the batch-block dimension is OUTERMOST, so the
    # resident weights serve every batch block (serving slots) from one DMA.
    t = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((t == 0) & (j == 0) & (k == 0))
    def _load_state():
        h_scr[0] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Recurrent reduction: one (B, bk) x (bk, bn) MXU dot per gate against the
    # VMEM-resident weight block.  h_{t-1} comes from the t-parity scratch slot.
    h_prev = h_scr[t % 2, :, pl.ds(k * bk, bk)]                # (B, bk)
    for g in range(4):
        acc_ref[g] += jax.lax.dot_general(
            h_prev, w_ref[g, pl.ds(j * bn, bn), pl.ds(k * bk, bk)],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _elementwise():
        sl = pl.ds(j * bn, bn)
        pre = acc_ref[...] + pre_x_ref[0].astype(jnp.float32)  # (4, B, bn)
        peep = peep_ref[:, sl].astype(jnp.float32)             # (3, bn)
        bias = bias_ref[:, sl].astype(jnp.float32)             # (4, bn)
        c_prev = c_scr[:, sl]                                  # (B, bn)
        i = jax.nn.sigmoid(pre[0] + peep[0] * c_prev + bias[0])
        f = jax.nn.sigmoid(pre[1] + peep[1] * c_prev + bias[1])
        g = jnp.tanh(pre[2] + bias[2])
        c_new = f * c_prev + i * g
        o = jax.nn.sigmoid(pre[3] + peep[2] * c_new + bias[3])
        h_new = o * jnp.tanh(c_new)
        # Masked step = identity on the resident state (select, no arithmetic
        # — the all-ones mask path stays bit-identical to the unmasked form).
        # The (B, 1) mask column is broadcast while still numeric: Mosaic
        # cannot relayout a boolean vector into a column.
        m = jnp.broadcast_to(mask_ref[0], h_new.shape) > 0     # (B, bn)
        h_new = jnp.where(m, h_new, h_scr[t % 2, :, sl])
        c_new = jnp.where(m, c_new, c_prev)
        h_scr[(t + 1) % 2, :, sl] = h_new
        c_scr[:, sl] = c_new
        hs_ref[0] = h_new.astype(hs_ref.dtype)
        cs_ref[0] = c_new.astype(cs_ref.dtype)


@functools.partial(jax.jit, static_argnames=('bn', 'bk', 'bb', 'interpret'))
def lstm_seq(pre_x: jax.Array, w_h: jax.Array, peep: jax.Array,
             bias: jax.Array, h0: jax.Array, c0: jax.Array,
             mask: Optional[jax.Array] = None, *,
             bn: int = 128, bk: int = 128, bb: Optional[int] = None,
             interpret: bool = False):
    """Whole-sequence fused LSTM.

    pre_x: (T, 4, B, N_h) hoisted ``W_x @ x_t + (0)`` pre-activations;
    w_h: (4, N_h, N_h); peep: (3, N_h); bias: (4, N_h); h0, c0: (B, N_h).
    N_h must be a multiple of both bn and bk; B a multiple of 8 and of the
    batch block ``bb`` (None = one block).  ``bb`` adds an outermost batch
    grid dimension: each block runs the full T-step recurrence against the
    same resident weights, so serving slots amortise one weight DMA.
    ``mask``: optional (T, B) validity mask (>0 = live step); a masked step
    carries h/c through unchanged and re-emits the carried values (None =
    all steps live, bit-identical to the masked call with an all-ones mask).
    Returns (hs, cs), each (T, B, N_h).
    """
    T, _, b, n_h = pre_x.shape
    bb = b if bb is None else bb
    assert n_h % bn == 0 and n_h % bk == 0, (n_h, bn, bk)
    assert b % bb == 0, (b, bb)
    if mask is None:
        mask = jnp.ones((T, b), pre_x.dtype)
    # (T, B, 1): a (1, bb, 1) block keeps the TPU (8, 128) block rule (the
    # last dim equals the array's), which a (1, bb) block of (T, B) breaks.
    mask = mask.astype(jnp.float32).reshape(T, b, 1)
    n_k = n_h // bk

    hs, cs = pl.pallas_call(
        functools.partial(_seq_kernel, n_k=n_k, bn=bn, bk=bk),
        grid=(b // bb, T, n_h // bn, n_k),
        in_specs=[
            pl.BlockSpec((1, 4, bb, bn), lambda nb, t, j, k: (t, 0, nb, j)),
            # Constant index maps: fetched once, resident for the whole grid.
            pl.BlockSpec((4, n_h, n_h), lambda nb, t, j, k: (0, 0, 0)),
            pl.BlockSpec((3, n_h), lambda nb, t, j, k: (0, 0)),
            pl.BlockSpec((4, n_h), lambda nb, t, j, k: (0, 0)),
            pl.BlockSpec((bb, n_h), lambda nb, t, j, k: (nb, 0)),
            pl.BlockSpec((bb, n_h), lambda nb, t, j, k: (nb, 0)),
            pl.BlockSpec((1, bb, 1), lambda nb, t, j, k: (t, nb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bb, bn), lambda nb, t, j, k: (t, nb, j)),
            pl.BlockSpec((1, bb, bn), lambda nb, t, j, k: (t, nb, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, b, n_h), pre_x.dtype),
            jax.ShapeDtypeStruct((T, b, n_h), pre_x.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, bb, n_h), jnp.float32),  # h double buffer (t parity)
            pltpu.VMEM((bb, n_h), jnp.float32),     # c, updated in place
            pltpu.VMEM((4, bb, bn), jnp.float32),   # gate pre-act accumulator
        ],
        compiler_params=vmem_compiler_params(
            w_h.size * w_h.dtype.itemsize + 3 * bb * n_h * 4),
        interpret=interpret,
    )(pre_x, w_h, peep, bias, h0, c0, mask)
    return hs, cs


# ---------------------------------------------------------------------------
# int8 kernel — bit-accurate systolic datapath (contribution C2)
# ---------------------------------------------------------------------------

_sat16 = quant.saturate_int16
_rshift_round = quant.rshift_round


def apply_lut_ref(lut_ref, q: jax.Array) -> jax.Array:
    """``quant.apply_lut`` inside a kernel, on STATE_FMT codes ``q``.

    ``lut_ref`` is a (1, 256) int32 table in SMEM.  Mosaic cannot lower the
    gather, so the lookup is 256 selects against scalar table entries —
    exactly ``lut[q + 128]``, bit for bit.  Returns int32.
    """
    idx = q.astype(jnp.int32) + 2 ** (quant.STATE_FMT.bits - 1)

    def pick(k, acc):
        return jnp.where(idx == k, lut_ref[0, k], acc)

    return jax.lax.fori_loop(0, lut_ref.shape[1], pick, jnp.zeros_like(idx))


def _seq_kernel_q(xs_ref, w_ref, peep_ref, bias_ref, sig_ref, tanh_ref,
                  h0_ref, c0_ref, mask_ref, hs_ref, cs_ref, h_scr, c_scr,
                  acc_ref, *, n_c: int, cols_x: int, tile: int):
    # Grid (NB, T, R, C) — batch blocks outermost, as in the f32 kernel.
    t = pl.program_id(1)
    r = pl.program_id(2)
    c = pl.program_id(3)

    @pl.when((t == 0) & (r == 0) & (c == 0))
    def _load_state():
        h_scr[0] = h0_ref[...]
        c_scr[...] = c0_ref[...]

    @pl.when(c == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Column input: x-region columns consume the streamed frame slice, h-region
    # columns read the resident hidden state (the chip's vertical re-broadcast).
    h_off = pl.multiple_of(jnp.maximum(c - cols_x, 0) * tile, tile)
    h_col = h_scr[t % 2, :, pl.ds(h_off, tile)]
    col_in = jnp.where(c < cols_x, xs_ref[0], h_col)

    # Per-engine tile MAC — int8 x int8 on the MXU, accumulated exactly in
    # int32 — saturated to the 16-bit value an engine hands to its row
    # neighbour, then the sequential saturating hop.
    for g in range(4):
        partial = _sat16(jax.lax.dot_general(
            col_in, w_ref[g, pl.ds(r * tile, tile), pl.ds(c * tile, tile)],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32))
        acc_ref[g] = _sat16(acc_ref[g] + partial)

    @pl.when(c == n_c - 1)
    def _elementwise():
        sl = pl.ds(r * tile, tile)
        c_prev32 = c_scr[:, sl].astype(jnp.int32)
        peep32 = peep_ref[:, sl].astype(jnp.int32)
        bias32 = bias_ref[:, sl].astype(jnp.int32)
        shift8 = ACC_FMT.frac_bits - quant.STATE_FMT.frac_bits

        def gate(idx, peep_idx, c_term, lut_ref):
            a = acc_ref[idx] + bias32[idx]
            if peep_idx is not None:
                a = a + peep32[peep_idx] * c_term
            a = _sat16(a)
            a8 = jnp.clip(_rshift_round(a, shift8), -128, 127)
            return apply_lut_ref(lut_ref, a8)

        i = gate(0, 0, c_prev32, sig_ref)
        f = gate(1, 1, c_prev32, sig_ref)
        g = gate(2, None, None, tanh_ref)
        fc = f * c_prev32                        # Q0.7 * Q2.5 -> frac 12
        ig = _rshift_round(i * g, 2)             # frac 14 -> 12
        c_new = _sat16(fc + ig)                  # Q3.12
        c_new8 = jnp.clip(
            _rshift_round(c_new, CELL_FMT.frac_bits - quant.STATE_FMT.frac_bits),
            -128, 127)
        o = gate(3, 2, c_new8, sig_ref)
        tanh_c = apply_lut_ref(tanh_ref, c_new8)
        h_new = _rshift_round(o * tanh_c, 14 - quant.STATE_FMT.frac_bits)
        h8 = jnp.clip(h_new, -128, 127).astype(jnp.int8)

        # Masked step = identity on the resident codes (pure select — the
        # all-ones mask path stays bit-identical to the unmasked datapath).
        m = jnp.broadcast_to(mask_ref[0], h8.shape) > 0
        h8 = jnp.where(m, h8, h_scr[t % 2, :, sl])
        c8 = jnp.where(m, c_new8.astype(jnp.int8), c_scr[:, sl])

        h_scr[(t + 1) % 2, :, sl] = h8
        c_scr[:, sl] = c8
        hs_ref[0] = h8
        cs_ref[0] = c8


@functools.partial(jax.jit, static_argnames=('tile', 'cols_x', 'bb',
                                             'interpret'))
def lstm_seq_quantized(xs_q: jax.Array, w_q: jax.Array, peep_q: jax.Array,
                       bias_q: jax.Array, sig_lut: jax.Array,
                       tanh_lut: jax.Array,
                       h0_q: Optional[jax.Array] = None,
                       c0_q: Optional[jax.Array] = None,
                       mask: Optional[jax.Array] = None, *, tile: int,
                       cols_x: int, bb: Optional[int] = None,
                       interpret: bool = False):
    """Whole-sequence bit-accurate int8 LSTM.

    xs_q: (T, B, padded_x) int8 frame codes; w_q: (4, padded_h, padded_in) int8
    dense engine-tile layout (``[W_x | W_h]`` with the x-region padded to whole
    tiles); peep_q: (3, padded_h) int8; bias_q: (4, padded_h) int16 in ACC_FMT;
    sig_lut/tanh_lut: (1, 256) int8; ``bb`` an optional batch block (B must
    divide by it; batch blocks iterate outermost so the resident weights are
    fetched once).  ``h0_q``/``c0_q``: optional (B, padded_h) int8 state codes
    carried in from a previous chunk (None = zero state); ``mask``: optional
    (T, B) int8 validity mask — a masked step carries the codes through
    unchanged (pure select, so the all-ones mask is bit-identical to None).
    Returns (hs_q, cs_q), each (T, B, padded_h) int8, bit-identical to
    scanning ``core.systolic.systolic_cell_quantized`` from the given state.
    """
    T, b, padded_x = xs_q.shape
    _, padded_h, padded_in = w_q.shape
    assert padded_x == cols_x * tile and padded_in % tile == 0
    bb = b if bb is None else bb
    assert b % bb == 0, (b, bb)
    if h0_q is None:
        h0_q = jnp.zeros((b, padded_h), jnp.int8)
    if c0_q is None:
        c0_q = jnp.zeros((b, padded_h), jnp.int8)
    if mask is None:
        mask = jnp.ones((T, b), jnp.int8)
    mask = mask.astype(jnp.int32).reshape(T, b, 1)   # (1, bb, 1) blocks
    n_c = padded_in // tile

    return pl.pallas_call(
        functools.partial(_seq_kernel_q, n_c=n_c, cols_x=cols_x, tile=tile),
        grid=(b // bb, T, padded_h // tile, n_c),
        in_specs=[
            pl.BlockSpec((1, bb, tile),
                         lambda nb, t, r, c: (t, nb, jnp.minimum(c, cols_x - 1))),
            pl.BlockSpec((4, padded_h, padded_in),
                         lambda nb, t, r, c: (0, 0, 0)),
            pl.BlockSpec((3, padded_h), lambda nb, t, r, c: (0, 0)),
            pl.BlockSpec((4, padded_h), lambda nb, t, r, c: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # sigmoid LUT
            pl.BlockSpec(memory_space=pltpu.SMEM),   # tanh LUT
            pl.BlockSpec((bb, padded_h), lambda nb, t, r, c: (nb, 0)),
            pl.BlockSpec((bb, padded_h), lambda nb, t, r, c: (nb, 0)),
            pl.BlockSpec((1, bb, 1), lambda nb, t, r, c: (t, nb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bb, tile), lambda nb, t, r, c: (t, nb, r)),
            pl.BlockSpec((1, bb, tile), lambda nb, t, r, c: (t, nb, r)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, b, padded_h), jnp.int8),
            jax.ShapeDtypeStruct((T, b, padded_h), jnp.int8),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, bb, padded_h), jnp.int8),  # h codes, t parity
            pltpu.VMEM((bb, padded_h), jnp.int8),     # c codes
            pltpu.VMEM((4, bb, tile), jnp.int32),     # saturating accumulator
        ],
        compiler_params=vmem_compiler_params(w_q.size + 3 * bb * padded_h),
        interpret=interpret,
    )(xs_q, w_q, peep_q, bias_q, sig_lut.astype(jnp.int32),
      tanh_lut.astype(jnp.int32), h0_q, c0_q, mask)
