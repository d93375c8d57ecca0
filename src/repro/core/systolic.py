"""Systolic LSTM execution — Chipmunk contributions C1 + C3.

The paper executes one LSTM on an R x C grid of engines.  Each engine holds a
``tile x tile`` block of the packed 4-gate weight matrix ``W = [W_x | W_h]`` in local
SRAM (weight-stationary).  Per timestep:

  1. the packed input vector ``xh = [x_t | h_{t-1}]`` is split into C column slices,
     each broadcast *down* a column of engines (paper Fig. 3a);
  2. every engine MACs its tile against its column slice (the sequential "column
     loop" of Sec. 3.2, run on 96 parallel row units);
  3. partial sums are accumulated *across* each row of engines in 16-bit saturating
     arithmetic (the systolic hop), finishing at the last column (Fig. 3b);
  4. the finishing column applies the LUT nonlinearities and the element-wise state
     update (Eqs. 1-5) for its row chunk of ``h_t``/``c_t``;
  5. the new ``h_t`` chunks are re-broadcast vertically for the next timestep
     (Fig. 3c).  Only O(N_h) bytes ever cross engine boundaries.

TPU adaptation (see DESIGN.md §2): engines -> mesh devices on ("row", "col") axes;
step 3 -> ``lax.psum`` over "col"; step 5 -> ``lax.all_gather`` over "row".  The
pure-JAX tiled forms below are numerically identical and are what the production
pjit path lowers (XLA emits the same collective schedule from sharding constraints).

Four execution paths, all validated against ``core.lstm.lstm_cell``:
  * ``systolic_cell_tiled``        — float, per-tile partials + row reduction.
  * ``systolic_cell_quantized``    — bit-accurate int8 storage / int16 saturating
                                     hops / LUT activations (contribution C2).
  * ``systolic_lstm_shard_map``    — per-step distributed baseline over an
                                     explicit ("row","col") mesh: one scan step
                                     per timestep, the packed ``[x|h]`` column
                                     re-assembled (and the x-region re-MACed)
                                     every step.
  * ``systolic_lstm_seq``          — the multi-engine scale-out of the
                                     persistent whole-sequence kernel
                                     (DESIGN.md §6): ``W_x @ x`` hoisted out of
                                     the time loop, each device's weight block
                                     tile-stationary for all T steps, per-step
                                     ``psum`` over "col" and ``all_gather`` of
                                     the ``h_t`` chunks over "row".  The int8
                                     form (``systolic_lstm_seq_quantized``)
                                     replays the 16-bit saturating hop in
                                     engine order and is bit-identical to
                                     ``systolic_cell_quantized``.

A process-level mesh registry (``install_mesh`` / ``current_mesh``) lets the
backend dispatch in ``core.lstm`` auto-select the scale-out path whenever a
systolic mesh is installed (``launch/mesh.py`` topology presets).

The STAGED scale-out (``systolic_lstm_stack_seq`` and its int8 twin,
DESIGN.md §9) composes the above with the §8 fused wavefront stack: each
stage of a ``(stage, row, col)`` mesh holds one contiguous layer block
weight-stationary and runs the fused composition with the §6 row/col
dataflow inside the stage, while the hidden-state sequence pipelines across
stages in chunks handed over by ``ppermute`` — the paper's full 3x(5x5)
Table-2 topology (``graves-75``) as one dispatch path.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from . import quant
from .lstm import GATES, I, F, G, O, PEEP_I, PEEP_F, PEEP_O, LSTMParams

N_LSTM_SILICON = 96  # rows per engine in the fabricated chip


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# ---------------------------------------------------------------------------
# Process-level systolic mesh registry (DESIGN.md §6)
# ---------------------------------------------------------------------------

_INSTALLED_MESH: Optional[Mesh] = None


def install_mesh(mesh: Mesh) -> Mesh:
    """Register ``mesh`` as the process-wide systolic mesh and return it.

    ``core.lstm.select_lstm_backend`` consults this registry: when an
    installed mesh admits the layer (``seq_scaleout_admissible``), ``auto``
    resolves to the ``pallas_seq_systolic`` backend.  Numerics are unchanged
    by installation — only dispatch is affected.
    """
    global _INSTALLED_MESH
    _INSTALLED_MESH = mesh
    return mesh


def current_mesh() -> Optional[Mesh]:
    """The installed systolic mesh, or None (dispatch then never scales out)."""
    return _INSTALLED_MESH


def clear_mesh() -> None:
    """Uninstall the systolic mesh (dispatch reverts to single-engine rules)."""
    global _INSTALLED_MESH
    _INSTALLED_MESH = None


def seq_scaleout_admissible(n_h: int, mesh: Optional[Mesh], *,
                            n_layers: Optional[int] = None,
                            n_x: int = 0, T: int = 0, batch: int = 0,
                            row_axis: str = 'row', col_axis: str = 'col',
                            stage_axis: str = 'stage',
                            die_axis: str = 'die',
                            vmem_budget: Optional[int] = None) -> bool:
    """Tile-admission rule for the systolic scale-outs (DESIGN.md §6, §9).

    Single-layer form (``n_layers=None``, consulted by per-layer ``auto``
    dispatch for ``systolic_lstm_seq``): True iff ``mesh`` has the two
    systolic axes, no other axis is >1 (a live "stage" axis belongs to the
    stack-level rule below), at least one systolic axis is >1 (an all-1
    mesh degenerates to the single-engine kernel, whose §3.3
    platform/shape rules must keep deciding — interpret-mode emulation
    must never be auto-picked on CPU), and one device's resident block —
    4 gate ``bn x bk`` tiles plus the row slice of peepholes/biases, where
    ``bn = n_h_p/rows`` and ``bk = n_h_p/cols`` — fits the VMEM budget.

    Stage-aware fused form (``n_layers`` given, consulted by
    ``select_stack_backend`` for ``systolic_lstm_stack_seq``): admits the
    STAGED scale-out of the fused stack iff the mesh's ``stage`` axis is
    live (>=2 — a stage-1 mesh is the layerwise §6 rule's domain) but not
    deeper than the stack (an idle stage would only add pipeline bubbles),
    no axis beyond (stage, row, col) is >1, and one device's resident
    layer block — ``ceil(n_layers/stages)`` layers' worth of BOTH weight
    families (``W_h`` and ``W_in`` blocks) plus their peephole/bias rows —
    fits the VMEM budget.  Admission never changes numerics, only whether
    ``auto`` dispatch picks a scale-out backend.

    When shape context (``n_x``/``T``/``batch``) is supplied, the staged
    check sizes the bottleneck stage from a tuned uneven split
    (``resolve_staged_blocks``) instead of the balanced ceiling — the
    tuned ``max(counts)`` is >= the balanced ``ceil(L/S)``, so a tuned
    split can only make admission stricter, never admit a config the
    balanced default would reject on a colder cache.  The guard stays
    authoritative either way.

    Die-aware form (§14): a 4-axis ("die","stage","row","col") fleet mesh
    (``launch.mesh.DieMesh.full_mesh``) is admitted by the staged rule with
    the die axis FOLDED into the pipeline depth — execution always runs on
    the flattened healthy-dies submesh where ``stages = dies * stage``, so
    admission models exactly what dispatch will run.  The single-layer rule
    still rejects any live die axis (a fleet belongs to the staged path).
    """
    if mesh is None:
        return False
    names = mesh.axis_names
    if vmem_budget is None:
        from .lstm import _VMEM_BUDGET_BYTES as vmem_budget
    if n_layers is not None:
        if (row_axis not in names or col_axis not in names
                or stage_axis not in names):
            return False
        if any(mesh.shape[a] > 1 for a in names
               if a not in (row_axis, col_axis, stage_axis, die_axis)):
            return False
        stages = mesh.shape[stage_axis]
        if die_axis in names:
            stages *= mesh.shape[die_axis]
        if stages < 2 or stages > n_layers:
            return False
        mr, mc = mesh.shape[row_axis], mesh.shape[col_axis]
        n_h_p = _round_up(n_h, math.lcm(mr, mc))
        bn, bk = n_h_p // mr, n_h_p // mc
        lb = -(-n_layers // stages)
        tuned = resolve_staged_blocks(n_layers, T, stages, n_h=n_h,
                                      n_x=n_x, batch=batch, mesh=mesh)
        if tuned is not None:
            lb = max(max(tuned), lb)
        per_layer = 2 * GATES * bn * bk * 4 + (3 + GATES) * bn * 4
        return lb * per_layer <= vmem_budget
    try:
        mr, mc = _require_systolic_axes(mesh, row_axis, col_axis)
    except ValueError:
        return False
    if mr == 1 and mc == 1:
        return False
    n_h_p = _round_up(n_h, math.lcm(mr, mc))
    bn, bk = n_h_p // mr, n_h_p // mc
    return GATES * bn * bk * 4 + (3 + GATES) * bn * 4 <= vmem_budget


# ---------------------------------------------------------------------------
# Tiling plan + weight packing
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SystolicPlan:
    """Block layout of one LSTM layer on an R x C engine grid.

    The x-region of the packed input is padded to a whole number of tiles so the
    h-region starts tile-aligned: column c < cols_x consumes input-state slices,
    column c >= cols_x consumes hidden-state slices (which is what makes step 5's
    vertical re-broadcast wiring static — "hard-wired" in the paper's words).
    """

    n_x: int
    n_h: int
    tile: int = N_LSTM_SILICON

    @property
    def rows(self) -> int:  # R: output (hidden) chunks
        return math.ceil(self.n_h / self.tile)

    @property
    def cols_x(self) -> int:
        return math.ceil(self.n_x / self.tile)

    @property
    def cols_h(self) -> int:
        return math.ceil(self.n_h / self.tile)

    @property
    def cols(self) -> int:  # C: input chunks
        return self.cols_x + self.cols_h

    @property
    def padded_h(self) -> int:
        return self.rows * self.tile

    @property
    def padded_x(self) -> int:
        return self.cols_x * self.tile

    @property
    def padded_in(self) -> int:
        return self.cols * self.tile

    @property
    def n_engines(self) -> int:
        return self.rows * self.cols

    def weight_bytes_per_engine(self) -> int:
        # 4 gate tiles + row slice of peepholes (3) and biases (4, 16-bit)
        return GATES * self.tile * self.tile + 3 * self.tile + 4 * 2 * self.tile


class PackedLSTM(NamedTuple):
    """Weight tiles in engine layout (a lossless relayout of LSTMParams)."""

    tiles: jax.Array   # (R, C, 4, tile, tile)
    peep: jax.Array    # (R, 3, tile)
    bias: jax.Array    # (R, 4, tile)
    plan_shape: Tuple[int, int, int, int]  # (n_x, n_h, tile, cols_x) — static metadata

    @property
    def plan(self) -> SystolicPlan:
        n_x, n_h, tile, _ = self.plan_shape
        return SystolicPlan(n_x, n_h, tile)


def pack_lstm(params: LSTMParams, plan: SystolicPlan) -> PackedLSTM:
    """Block [W_x | W_h] into (R, C, 4, t, t) engine tiles (zero padding).

    Layout-only and lossless: every downstream execution path over the packed
    form reproduces ``core.lstm.lstm_cell`` on the original parameters.
    """
    t = plan.tile
    w = jnp.zeros((GATES, plan.padded_h, plan.padded_in), params.w_x.dtype)
    w = w.at[:, :params.w_x.shape[1], :plan.n_x].set(params.w_x)
    w = w.at[:, :params.w_h.shape[1], plan.padded_x:plan.padded_x + plan.n_h].set(params.w_h)
    tiles = w.reshape(GATES, plan.rows, t, plan.cols, t).transpose(1, 3, 0, 2, 4)
    peep = jnp.zeros((3, plan.padded_h), params.w_peep.dtype
                     ).at[:, :plan.n_h].set(params.w_peep)
    bias = jnp.zeros((GATES, plan.padded_h), params.b.dtype
                     ).at[:, :plan.n_h].set(params.b)
    return PackedLSTM(
        tiles=tiles,
        peep=peep.reshape(3, plan.rows, t).transpose(1, 0, 2),
        bias=bias.reshape(GATES, plan.rows, t).transpose(1, 0, 2),
        plan_shape=(plan.n_x, plan.n_h, plan.tile, plan.cols_x),
    )


def pack_xh(x: jax.Array, h: jax.Array, plan: SystolicPlan) -> jax.Array:
    """(..., n_x), (..., n_h) -> column blocks (..., C, tile).

    Pure zero-padded relayout (exactly inverted by ``unpack_h`` on the
    h-region); introduces no arithmetic.
    """
    batch = x.shape[:-1]
    xh = jnp.zeros(batch + (plan.padded_in,), x.dtype)
    xh = xh.at[..., :plan.n_x].set(x)
    xh = xh.at[..., plan.padded_x:plan.padded_x + plan.n_h].set(h)
    return xh.reshape(batch + (plan.cols, plan.tile))


def unpack_h(h_blocks: jax.Array, plan: SystolicPlan) -> jax.Array:
    """(..., R, tile) -> (..., n_h): drops the zero padding, no arithmetic."""
    return h_blocks.reshape(h_blocks.shape[:-2] + (plan.padded_h,))[..., :plan.n_h]


# ---------------------------------------------------------------------------
# Float tiled execution (paper dataflow, fp arithmetic)
# ---------------------------------------------------------------------------

def systolic_cell_tiled(packed: PackedLSTM, x_t: jax.Array, h_prev: jax.Array,
                        c_prev_blocks: jax.Array
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One timestep in the systolic dataflow, float arithmetic.

    Numerics contract: allclose to ``core.lstm.lstm_cell`` on the unpacked
    parameters (same math, re-associated per tile).  c_prev_blocks:
    (..., R, tile).  Returns (h_full (..., n_h), h_blocks, c_blocks).
    """
    plan = packed.plan
    xh = pack_xh(x_t, h_prev, plan)                       # steps 1: column slices
    # step 2: per-engine MAC; step 3: row accumulation (sum over c).
    pre = jnp.einsum('rcgij,...cj->...rgi', packed.tiles, xh)
    peep, b = packed.peep, packed.bias
    # step 4: gate nonlinearities + element-wise state update per row chunk.
    i = jax.nn.sigmoid(pre[..., I, :] + peep[:, PEEP_I] * c_prev_blocks + b[:, I])
    f = jax.nn.sigmoid(pre[..., F, :] + peep[:, PEEP_F] * c_prev_blocks + b[:, F])
    g = jnp.tanh(pre[..., G, :] + b[:, G])
    c_t = f * c_prev_blocks + i * g
    o = jax.nn.sigmoid(pre[..., O, :] + peep[:, PEEP_O] * c_t + b[:, O])
    h_blocks = o * jnp.tanh(c_t)
    return unpack_h(h_blocks, plan), h_blocks, c_t       # step 5 done by caller


def systolic_layer_tiled(packed: PackedLSTM, xs: jax.Array) -> jax.Array:
    """Scan the tiled cell over time.  xs: (T, ..., n_x) -> (T, ..., n_h).

    Allclose to ``core.lstm.lstm_layer`` and the float reference for the
    distributed forms (``systolic_lstm_shard_map``, ``systolic_lstm_seq``).
    """
    plan = packed.plan
    batch = xs.shape[1:-1]
    h0 = jnp.zeros(batch + (plan.n_h,), xs.dtype)
    c0 = jnp.zeros(batch + (plan.rows, plan.tile), xs.dtype)

    def step(carry, x_t):
        h, c = carry
        h, _, c = systolic_cell_tiled(packed, x_t, h, c)
        return (h, c), h

    _, hs = jax.lax.scan(step, (h0, c0), xs)
    return hs


# ---------------------------------------------------------------------------
# Bit-accurate quantized execution (contribution C2)
# ---------------------------------------------------------------------------

# Fixed-point layout (see quant.py): weights/states Q2.5 (int8), gates Q0.7 (int8),
# accumulator Q5.10 (int16, saturating at every inter-engine hop).
ACC_FMT = quant.QFormat(int_bits=5, frac_bits=10)
CELL_FMT = quant.QFormat(int_bits=3, frac_bits=12)  # f*c / i*g alignment format


class QuantizedPackedLSTM(NamedTuple):
    """Engine tiles in the silicon's fixed-point formats (see quantize_packed)."""

    tiles_q: jax.Array  # int8 (R, C, 4, t, t)
    peep_q: jax.Array   # int8 (R, 3, t)
    bias_q: jax.Array   # int16 (R, 4, t)  in ACC_FMT
    sig_lut: jax.Array  # int8 (256,)
    tanh_lut: jax.Array  # int8 (256,)
    plan_shape: Tuple[int, int, int, int]

    @property
    def plan(self) -> SystolicPlan:
        n_x, n_h, tile, _ = self.plan_shape
        return SystolicPlan(n_x, n_h, tile)


def quantize_packed(packed: PackedLSTM) -> QuantizedPackedLSTM:
    """Quantize engine tiles to the silicon formats (weights/peep Q2.5 int8,
    biases Q5.10 int16, LUT tables for the activations).  Deterministic
    round-to-nearest; every int8 execution path below consumes exactly these
    codes, so they all share one quantization error budget."""
    wf, sf = quant.WEIGHT_FMT, quant.STATE_FMT
    bias_codes = jnp.clip(
        jnp.round(packed.bias / ACC_FMT.scale),
        -(2 ** 15), 2 ** 15 - 1).astype(jnp.int16)
    sig, tanh = quant.default_luts(sf)
    return QuantizedPackedLSTM(
        tiles_q=quant.quantize(packed.tiles, wf),
        peep_q=quant.quantize(packed.peep, wf),
        bias_q=bias_codes,
        sig_lut=sig, tanh_lut=tanh,
        plan_shape=packed.plan_shape,
    )


def _sat16(x):
    return quant.saturate_int16(x)


_rshift_round = quant.rshift_round


def _quantized_state_update(pre_acc, c_prev32, peep32, bias32, sig_lut,
                            tanh_lut):
    """Silicon elementwise epilogue: gates -> LUTs -> c_t -> h_t, int only.

    Single source of truth for the bit-exact datapath tail: called by the
    per-step ``systolic_cell_quantized`` AND replayed verbatim by the
    distributed ``systolic_lstm_seq_quantized``, so the two stay bit-identical
    by construction.  pre_acc: (..., R, 4, t) int32 in ACC_FMT; c_prev32:
    (..., R, t) int32 codes; peep32: (..., R, 3, t); bias32: (..., R, 4, t).
    Returns (h_blocks8, c_new8), both int8 codes in STATE_FMT.
    """
    def gate(idx, peep_idx, c_term, lut):
        a = pre_acc[..., idx, :] + bias32[..., idx, :]
        if peep_idx is not None:
            a = a + peep32[..., peep_idx, :] * c_term  # Q2.5 * Q2.5, aligned
        a = _sat16(a)
        a8 = _rshift_round(a, ACC_FMT.frac_bits - quant.STATE_FMT.frac_bits)
        a8 = jnp.clip(a8, -128, 127)
        return quant.apply_lut(lut, a8, quant.STATE_FMT).astype(jnp.int32)

    i = gate(I, PEEP_I, c_prev32, sig_lut)
    f = gate(F, PEEP_F, c_prev32, sig_lut)
    g = gate(G, None, None, tanh_lut)

    # c_t = f.c + i.g : align Q0.7*Q2.5 (frac 12) with Q0.7*Q0.7 (frac 14) >> 2.
    fc = f * c_prev32                       # frac 12
    ig = _rshift_round(i * g, 2)            # frac 14 -> 12
    c_new = _sat16(fc + ig)                 # Q3.12
    c_new8 = jnp.clip(_rshift_round(c_new, CELL_FMT.frac_bits -
                                    quant.STATE_FMT.frac_bits), -128, 127)

    o = gate(O, PEEP_O, c_new8, sig_lut)
    tanh_c = quant.apply_lut(tanh_lut, c_new8, quant.STATE_FMT).astype(jnp.int32)
    h_new = _rshift_round(o * tanh_c, 14 - quant.STATE_FMT.frac_bits)
    h_blocks8 = jnp.clip(h_new, -128, 127).astype(jnp.int8)
    return h_blocks8, c_new8.astype(jnp.int8)


def systolic_cell_quantized(qp: QuantizedPackedLSTM, x_q: jax.Array,
                            h_q: jax.Array, c_q_blocks: jax.Array
                            ) -> Tuple[jax.Array, jax.Array]:
    """One timestep in integer arithmetic, per the silicon datapath.

    This is the bit-exactness REFERENCE: ``systolic_layer_quantized``,
    ``kernels.lstm_seq.lstm_layer_seq_quantized`` and
    ``systolic_lstm_seq_quantized`` are all bit-identical to scanning it.
    x_q: (..., n_x) int8 codes (Q2.5); h_q: (..., n_h) int8; c_q_blocks:
    (..., R, t) int8.  Returns (h_q_new, c_q_blocks_new).  All intermediate
    semantics follow the 16-bit saturating accumulator of the chip.
    """
    plan = qp.plan
    xh_q = pack_xh(x_q, h_q, plan)  # (..., C, t) int8

    # Per-engine tile MAC in wide arithmetic (int32), then saturate to 16 bit —
    # the value an engine hands to its row neighbour.
    partials = jnp.einsum('rcgij,...cj->...rcgi', qp.tiles_q.astype(jnp.int32),
                          xh_q.astype(jnp.int32))
    partials = _sat16(partials)

    # Sequential saturating row accumulation (hop order matters for saturation).
    def hop(acc, p_c):
        return _sat16(acc + p_c), None

    partials_c_first = jnp.moveaxis(partials, -3, 0)  # (C, ..., R, 4, t)
    acc0 = jnp.zeros(partials_c_first.shape[1:], jnp.int32)
    pre_acc, _ = jax.lax.scan(hop, acc0, partials_c_first)  # (..., R, 4, t) Q5.10

    h_blocks8, c_new8 = _quantized_state_update(
        pre_acc, c_q_blocks.astype(jnp.int32), qp.peep_q.astype(jnp.int32),
        qp.bias_q.astype(jnp.int32), qp.sig_lut, qp.tanh_lut)
    return unpack_h(h_blocks8, plan), c_new8


def systolic_layer_quantized(qp: QuantizedPackedLSTM, xs_q: jax.Array) -> jax.Array:
    """Scan the integer cell over time.  xs_q: (T, ..., n_x) int8 -> int8 hidden.

    Bit-identical by construction to ``systolic_cell_quantized`` stepped with
    zero initial state; the whole-sequence and distributed int8 forms are
    tested against this function.
    """
    plan = qp.plan
    batch = xs_q.shape[1:-1]
    h0 = jnp.zeros(batch + (plan.n_h,), jnp.int8)
    c0 = jnp.zeros(batch + (plan.rows, plan.tile), jnp.int8)

    def step(carry, x_t):
        h, c = carry
        h, c = systolic_cell_quantized(qp, x_t, h, c)
        return (h, c), h

    _, hs = jax.lax.scan(step, (h0, c0), xs_q)
    return hs


# ---------------------------------------------------------------------------
# Distributed execution: shard_map over an explicit ("row","col") mesh
# ---------------------------------------------------------------------------

def make_systolic_mesh(rows: int, cols: int, stage: int = 1,
                       devices=None) -> Mesh:
    """Build a (stage, row, col) mesh from the first stage*rows*cols devices.

    This is how the paper's own geometries (5x5, 3x(5x5)) are laid onto a pod:
    a rectangular sub-grid of the available chips.  Device order is row-major,
    which is what makes the ``all_gather`` chunk order of the distributed
    paths line up with the engine-tile row order (a pure layout guarantee —
    no numerics of its own).  ``launch/mesh.py`` exposes named topology
    presets (including ``graves-75``) built on this constructor.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    need = stage * rows * cols
    if len(devices) < need:
        raise ValueError(f'need {need} devices, have {len(devices)}')
    arr = np.array(devices[:need]).reshape(stage, rows, cols)
    return Mesh(arr, ('stage', 'row', 'col'),
                axis_types=(AxisType.Auto,) * 3)


def shard_packed_lstm(packed: PackedLSTM, mesh: Mesh) -> PackedLSTM:
    """Place weight tiles so engine (r, c) owns tile (r, c) — weight-stationary.

    Pure placement (device_put with a NamedSharding); values are unchanged.
    """
    from jax.sharding import NamedSharding
    tiles = jax.device_put(packed.tiles, NamedSharding(mesh, P('row', 'col')))
    peep = jax.device_put(packed.peep, NamedSharding(mesh, P('row')))
    bias = jax.device_put(packed.bias, NamedSharding(mesh, P('row')))
    return PackedLSTM(tiles, peep, bias, packed.plan_shape)


def systolic_lstm_shard_map(packed: PackedLSTM, mesh: Mesh, xs: jax.Array,
                            row_axis: str = 'row', col_axis: str = 'col'):
    """PER-STEP distributed baseline with the paper's communication pattern.

    Allclose to scanning ``systolic_cell_tiled`` (float re-association only).
    Every timestep re-assembles the packed ``[x|h]`` column and re-MACs the
    x-region against its weight columns — the per-step streaming cost the
    persistent ``systolic_lstm_seq`` (DESIGN.md §6) eliminates by hoisting
    ``W_x @ x`` out of the loop.  Kept as the scale-out benchmark baseline.

    xs: (T, B, padded_in) — the x-region columns carry data, h-region columns are
    zero (they are overwritten by the vertical h re-broadcast each step).
    Requires plan.rows == mesh row size and plan.cols == mesh col size.
    """
    plan = packed.plan
    t = plan.tile
    T, B = xs.shape[0], xs.shape[1]
    assert xs.shape[2] == plan.padded_in
    assert mesh.shape[row_axis] == plan.rows and mesh.shape[col_axis] == plan.cols

    def local_step(w_tile, peep_r, bias_r, xh_col, h_full, c_row):
        """SPMD body on engine (r, c).

        w_tile: (4, t, t); peep_r: (3, t); bias_r: (4, t); xh_col: (B, t);
        h_full: (B, padded_h) — replicated; c_row: (B, t).
        """
        c_idx = jax.lax.axis_index(col_axis)
        # h-region columns take their slice of the re-broadcast hidden state.
        h_off = jnp.maximum(c_idx - plan.cols_x, 0) * t
        h_slice = jax.lax.dynamic_slice(h_full, (0, h_off), (B, t))
        col_in = jnp.where(c_idx < plan.cols_x, xh_col, h_slice)

        partial = jnp.einsum('gij,bj->bgi', w_tile, col_in)       # column loop
        pre = jax.lax.psum(partial, col_axis)                      # row hops
        i = jax.nn.sigmoid(pre[:, I] + peep_r[PEEP_I] * c_row + bias_r[I])
        f = jax.nn.sigmoid(pre[:, F] + peep_r[PEEP_F] * c_row + bias_r[F])
        g = jnp.tanh(pre[:, G] + bias_r[G])
        c_new = f * c_row + i * g
        o = jax.nn.sigmoid(pre[:, O] + peep_r[PEEP_O] * c_new + bias_r[O])
        h_new = o * jnp.tanh(c_new)
        # Vertical re-broadcast of the updated hidden state (paper Fig. 3c).
        h_full_new = jax.lax.all_gather(h_new, row_axis, axis=1, tiled=True)
        return h_full_new, c_new

    def sharded_scan(tiles, peep, bias, xs_sharded):
        w_tile = tiles[0, 0]          # local block after sharding
        peep_r, bias_r = peep[0], bias[0]
        h0 = jnp.zeros((B, plan.padded_h), xs.dtype)
        c0 = jnp.zeros((B, t), xs.dtype)

        def step(carry, x_t):
            h_full, c_row = carry
            h_full, c_row = local_step(w_tile, peep_r, bias_r, x_t, h_full, c_row)
            return (h_full, c_row), h_full

        (_, _), hs = jax.lax.scan(step, (h0, c0), xs_sharded)
        return hs

    other_axes = tuple(n for n in mesh.axis_names if n not in (row_axis, col_axis))
    if any(mesh.shape[a] > 1 for a in other_axes):
        raise ValueError('use systolic_pipeline for meshes with a stage axis')
    fn = shard_map(
        sharded_scan, mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(row_axis), P(row_axis),
                  P(None, None, col_axis)),
        out_specs=P(),
        check_vma=False,
    )
    hs = fn(packed.tiles, packed.peep, packed.bias, xs)
    return hs[..., :plan.n_h]


# ---------------------------------------------------------------------------
# Multi-engine scale-out of the persistent sequence kernel (DESIGN.md §6)
# ---------------------------------------------------------------------------

def _require_systolic_axes(mesh: Mesh, row_axis: str, col_axis: str) -> Tuple[int, int]:
    names = mesh.axis_names
    if row_axis not in names or col_axis not in names:
        raise ValueError(f'mesh axes {names} lack ({row_axis!r}, {col_axis!r})')
    if any(mesh.shape[a] > 1 for a in names if a not in (row_axis, col_axis)):
        raise ValueError('use systolic_pipeline for meshes with a stage axis')
    return mesh.shape[row_axis], mesh.shape[col_axis]


def _scaleout_blocks(n_h: int, mr: int, mc: int) -> Tuple[int, int, int]:
    """Pad N_h so both the row (output) and col (reduction) axes divide it."""
    n_h_p = _round_up(n_h, math.lcm(mr, mc))
    return n_h_p, n_h_p // mr, n_h_p // mc


def _scaleout_forward(static, w_h, w_peep, b, pre_x, h0, c0, mask=None):
    """Distributed whole-sequence forward (padded in, un-padded out).

    Numerics contract: allclose to scanning ``systolic_cell_tiled`` (and to
    ``core.lstm.lstm_layer``) — same per-block partial sums, with the "col"
    reduction performed by ``lax.psum`` instead of the einsum contraction.
    ``mask``: optional (T, B) validity mask (replicated); a masked step is
    identity on the carried state via ``jnp.where`` — no arithmetic on the
    carried values, so ``None`` and an all-ones mask are bit-identical.
    """
    mesh, row_axis, col_axis = static
    T, B, _, n_h = pre_x.shape
    mr, mc = mesh.shape[row_axis], mesh.shape[col_axis]
    n_h_p, bn, bk = _scaleout_blocks(n_h, mr, mc)
    pad = n_h_p - n_h

    w_p = jnp.pad(w_h, ((0, 0), (0, pad), (0, pad)))
    peep_p = jnp.pad(w_peep, ((0, 0), (0, pad)))
    bias_p = jnp.pad(b, ((0, 0), (0, pad)))
    pre_p = jnp.pad(pre_x, ((0, 0), (0, 0), (0, 0), (0, pad)))
    h0_p = jnp.pad(h0, ((0, 0), (0, pad)))
    c0_p = jnp.pad(c0, ((0, 0), (0, pad)))
    if mask is None:
        mask = jnp.ones((T, B), jnp.bool_)

    def body(w_blk, peep_blk, bias_blk, pre_blk, h0_full, c0_blk, mask_t):
        """SPMD body on engine-block (r, c).

        w_blk: (4, bn, bk) — tile-stationary for all T steps (the scan closes
        over it, so it is fetched once and revisited every timestep);
        pre_blk: (T, B, 4, bn) hoisted ``W_x @ x`` stream for row block r;
        mask_t: (T, B) replicated validity mask.
        """
        col = jax.lax.axis_index(col_axis)

        def step(carry, inp):
            h_full, c = carry
            pre_t, m = inp
            # Fig. 3a: this engine column consumes its static h-slice.
            h_k = jax.lax.dynamic_slice(h_full, (0, col * bk), (B, bk))
            part = jnp.einsum('gnk,bk->bgn', w_blk, h_k)
            # Fig. 3b: row accumulation of partial sums across engine columns.
            pre = jax.lax.psum(part, col_axis) + pre_t
            i = jax.nn.sigmoid(pre[:, I] + peep_blk[PEEP_I] * c + bias_blk[I])
            f = jax.nn.sigmoid(pre[:, F] + peep_blk[PEEP_F] * c + bias_blk[F])
            g = jnp.tanh(pre[:, G] + bias_blk[G])
            c_new = f * c + i * g
            o = jax.nn.sigmoid(pre[:, O] + peep_blk[PEEP_O] * c_new + bias_blk[O])
            h_new = o * jnp.tanh(c_new)
            # Fig. 3c: vertical re-broadcast of the updated hidden chunks.
            h_full_new = jax.lax.all_gather(h_new, row_axis, axis=1, tiled=True)
            # Masked step = identity on the carried state (ragged serving).
            m = m[:, None]
            h_full_new = jnp.where(m, h_full_new, h_full)
            c_new = jnp.where(m, c_new, c)
            return (h_full_new, c_new), (h_full_new, c_new)

        (h_T, c_T), (hs, cs) = jax.lax.scan(step, (h0_full, c0_blk),
                                            (pre_blk, mask_t))
        return hs, cs, h_T, c_T

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, row_axis, col_axis), P(None, row_axis),
                  P(None, row_axis), P(None, None, None, row_axis),
                  P(None, None), P(None, row_axis), P(None, None)),
        out_specs=(P(), P(None, None, row_axis), P(), P(None, row_axis)),
        check_vma=False,
    )
    hs, cs, h_T, c_T = fn(w_p, peep_p, bias_p, pre_p, h0_p, c0_p, mask)
    return hs[..., :n_h], cs[..., :n_h], h_T[..., :n_h], c_T[..., :n_h]


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def systolic_seq_fused(static, w_h, w_peep, b, pre_x, h0, c0):
    """Distributed whole-sequence LSTM with the production training VJP.

    Same contract as ``kernels.lstm_seq.lstm_seq_fused`` (forward allclose to
    ``core.lstm.lstm_scan_fused``; backward recomputes gates from the saved
    h/c trajectories via ``lstm_bwd_recompute_gates``), but the forward runs
    tile-stationary on the ``static = (mesh, row_axis, col_axis)`` grid.
    """
    hs, _, h_T, c_T = _scaleout_forward(static, w_h, w_peep, b, pre_x, h0, c0)
    return hs, (h_T, c_T)


def _sso_fwd(static, w_h, w_peep, b, pre_x, h0, c0):
    hs, cs, h_T, c_T = _scaleout_forward(static, w_h, w_peep, b, pre_x, h0, c0)
    return (hs, (h_T, c_T)), (w_h, w_peep, b, pre_x, hs, cs, h0, c0)


def _sso_bwd(static, res, grads):
    from .lstm import lstm_bwd_recompute_gates
    w_h, w_peep, b, pre_x, hs, cs, h0, c0 = res
    return lstm_bwd_recompute_gates(w_h, w_peep, b, pre_x, hs, cs, h0, c0,
                                    grads)


systolic_seq_fused.defvjp(_sso_fwd, _sso_bwd)


def systolic_lstm_seq(params: LSTMParams, mesh: Optional[Mesh], xs: jax.Array,
                      h0: Optional[jax.Array] = None,
                      c0: Optional[jax.Array] = None, *,
                      valid_len: Optional[jax.Array] = None,
                      row_axis: str = 'row', col_axis: str = 'col'
                      ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Whole-sequence persistent LSTM, scaled out over a systolic mesh.

    Drop-in for ``core.lstm.lstm_layer`` (xs: (T, B, N_x) -> (hs, (h_T, c_T)));
    output allclose to scanning ``systolic_cell_tiled`` and to ``lstm_layer``.
    Differentiable: the custom VJP recomputes gates from the h/c trajectories
    (identical to the ``pallas_seq`` backend's training path).

    The non-recurrent ``W_x @ x`` is hoisted out of the time loop as one wide
    matmul over the whole utterance; inside the loop each device MACs only its
    resident ``bn x bk`` recurrent block, row partials meet in a per-step
    ``psum`` over ``col_axis`` (Fig. 3b) and the updated ``h_t`` chunks are
    re-broadcast with ``all_gather`` over ``row_axis`` (Fig. 3c).  A ``None``
    or all-1 mesh degenerates to the single-engine Pallas sequence kernel
    (``kernels.lstm_seq.lstm_layer_seq``) — the composition this function
    scales out.

    ``valid_len``: optional (B,) per-stream valid lengths for ragged chunked
    serving (DESIGN.md §7) — steps ``t >= valid_len[b]`` are identity on the
    carried state, so ``(h_T, c_T)`` is the state after exactly
    ``valid_len[b]`` steps.  The masked path is inference-only (no VJP).
    """
    assert xs.ndim == 3, 'systolic_lstm_seq expects (T, B, N_x) input'
    T, B = xs.shape[0], xs.shape[1]
    n_h = params.n_h
    if h0 is None:
        h0 = jnp.zeros((B, n_h), xs.dtype)
    if c0 is None:
        c0 = jnp.zeros((B, n_h), xs.dtype)
    if mesh is None or all(s == 1 for s in mesh.shape.values()):
        from ..kernels.lstm_seq import lstm_layer_seq
        return lstm_layer_seq(params, xs, h0, c0, valid_len=valid_len)
    _require_systolic_axes(mesh, row_axis, col_axis)
    pre_x = jnp.einsum('ghx,tbx->tbgh', params.w_x, xs)   # hoisted input stream
    static = (mesh, row_axis, col_axis)
    if valid_len is not None:
        from .lstm import valid_len_mask
        mask = valid_len_mask(T, valid_len, B)
        hs, cs, h_T, c_T = _scaleout_forward(static, params.w_h,
                                             params.w_peep, params.b,
                                             pre_x, h0, c0, mask)
        return hs, (h_T, c_T)
    return systolic_seq_fused(static, params.w_h,
                              params.w_peep, params.b, pre_x, h0, c0)


def _x_prefix_fold(tiles_x: jax.Array, xcols: jax.Array) -> jax.Array:
    """Raw-array core of ``quantized_x_prefix``: per-tile int32 MACs
    saturated to int16, then the sequential engine-order hop over the
    x-region columns.  Single source of truth for the h-independent prefix
    of the saturating chain — ``quantized_x_prefix`` (host-side hoisting)
    and the staged scale-out's in-body below-region fold
    (``systolic_lstm_stack_seq_quantized``) both call it, so every
    consumer replays the identical saturation/hop order.  tiles_x:
    (R, C_x, 4, t, t) int8; xcols: (T, B, C_x, t) int8 ->
    (T, B, R, 4, t) int32 in ACC_FMT."""
    part_x = _sat16(jnp.einsum('rcgij,tbcj->ctbrgi',
                               tiles_x.astype(jnp.int32),
                               xcols.astype(jnp.int32)))

    def hop(acc, p):
        return _sat16(acc + p), None

    acc0 = jnp.zeros(part_x.shape[1:], jnp.int32)
    acc_x, _ = jax.lax.scan(hop, acc0, part_x)
    return acc_x


def quantized_x_prefix(qp: QuantizedPackedLSTM, xs_q: jax.Array) -> jax.Array:
    """Hoisted x-region prefix of the saturating hop chain — the first
    ``cols_x`` hops, which depend only on the frame stream, computed once
    for the whole sequence (the shared ``_x_prefix_fold``).  Bit-identical
    to folding those columns inside the step loop (the same ops in the same
    order), so every consumer — the §6 distributed form, the §8
    fused-stack kernel's layer 0, AND the §9 staged scale-out — resumes
    the chain from exactly the state the silicon would hold.
    xs_q: (T, B, n_x) int8 codes -> (T, B, R, 4, tile) int32 in ACC_FMT."""
    plan = qp.plan
    T, B = xs_q.shape[0], xs_q.shape[1]
    if not plan.cols_x:
        return jnp.zeros((T, B, plan.rows, GATES, plan.tile), jnp.int32)
    xs_pad = jnp.zeros((T, B, plan.padded_x), jnp.int8
                       ).at[..., :plan.n_x].set(xs_q)
    xcols = xs_pad.reshape(T, B, plan.cols_x, plan.tile)
    return _x_prefix_fold(qp.tiles_q[:, :plan.cols_x], xcols)


def systolic_lstm_seq_quantized(qp: QuantizedPackedLSTM, mesh: Optional[Mesh],
                                xs_q: jax.Array, *,
                                state: Optional[Tuple[jax.Array, jax.Array]] = None,
                                valid_len: Optional[jax.Array] = None,
                                return_state: bool = False,
                                row_axis: str = 'row',
                                col_axis: str = 'col'):
    """Distributed whole-sequence int8 LSTM, bit-identical to the silicon scan.

    xs_q: (T, B, n_x) int8 codes -> (T, B, n_h) int8 hidden codes, exactly
    equal (bit-identical) to scanning ``systolic_cell_quantized`` — and hence
    to ``systolic_layer_quantized`` and ``lstm_layer_seq_quantized``.

    The 16-bit saturating row accumulation (Fig. 3b) is order-sensitive, so a
    plain ``psum`` cannot be used: the x-region prefix of the hop chain (which
    does not depend on ``h``) is precomputed once for the whole sequence, and
    per step each device's h-region tile partials are ``all_gather``ed over
    ``col_axis`` and the hop replayed in engine order — the exact saturation
    schedule of the chip.  Requires ``plan.rows % mesh rows == 0`` and
    ``plan.cols_h % mesh cols == 0``.  A ``None``/all-1 mesh degenerates to
    ``kernels.lstm_seq.lstm_layer_seq_quantized``.

    Chunked streaming (DESIGN.md §7, same contract as the single-engine int8
    kernel): ``state`` is an opaque carry of ``(h_q, c_q)`` padded-layout
    int8 codes from a previous call with ``return_state=True`` (None = zero
    state); ``valid_len`` (B,) masks ragged tail steps per stream — a masked
    step is a pure select identity on the carried codes — so feeding a
    sequence chunk by chunk over the mesh is bit-identical to the monolithic
    call, and the §6 scale-out composes with the streaming engine.  With
    ``return_state=True`` returns ``(hs, (h_q, c_q))``.
    """
    plan = qp.plan
    if mesh is None or all(s == 1 for s in mesh.shape.values()):
        from ..kernels.lstm_seq import lstm_layer_seq_quantized
        return lstm_layer_seq_quantized(qp, xs_q, state=state,
                                        valid_len=valid_len,
                                        return_state=return_state)
    mr, mc = _require_systolic_axes(mesh, row_axis, col_axis)
    R, c_h, t = plan.rows, plan.cols_h, plan.tile
    if R % mr or c_h % mc:
        raise ValueError(f'engine grid {R}x{c_h} (h-region) does not divide '
                         f'mesh {mr}x{mc}')
    assert xs_q.ndim == 3, 'systolic_lstm_seq_quantized expects (T, B, n_x)'
    T, B = xs_q.shape[0], xs_q.shape[1]
    r_l, c_l = R // mr, c_h // mc
    if state is None:
        h0_q = jnp.zeros((B, plan.padded_h), jnp.int8)
        c0_q = jnp.zeros((B, plan.padded_h), jnp.int8)
    else:
        h0_q = state[0].reshape(B, plan.padded_h)
        c0_q = state[1].reshape(B, plan.padded_h)
    if valid_len is None:
        mask = jnp.ones((T, B), jnp.int8)
    else:
        from .lstm import valid_len_mask
        mask = valid_len_mask(T, valid_len, B).astype(jnp.int8)

    def hop(acc, p):
        return _sat16(acc + p), None

    acc_x = quantized_x_prefix(qp, xs_q)
    tiles_h = qp.tiles_q[:, plan.cols_x:]            # (R, c_h, 4, t, t)

    def body(tiles_blk, peep_blk, bias_blk, accx_blk, sig_lut, tanh_lut,
             h0_full, c0_blk, mask_t):
        """SPMD body: tiles_blk (r_l, c_l, 4, t, t) stationary for all T.

        h0_full: (B, padded_h) replicated carried codes; c0_blk: (B, r_l*t)
        this row block's carried cell codes; mask_t: (T, B) replicated.
        """
        col = jax.lax.axis_index(col_axis)
        peep32 = peep_blk.astype(jnp.int32)
        bias32 = bias_blk.astype(jnp.int32)

        def step(carry, inp):
            h_full, c_blk = carry
            accx_t, m = inp
            h_cols = jax.lax.dynamic_slice(
                h_full, (0, col * (c_l * t)), (B, c_l * t)).reshape(B, c_l, t)
            parts = _sat16(jnp.einsum('rlgij,blj->lbrgi',
                                      tiles_blk.astype(jnp.int32),
                                      h_cols.astype(jnp.int32)))
            # Engine-order saturating hop replay: gather every column's
            # partials, then fold them sequentially from the x-prefix.
            parts_all = jax.lax.all_gather(parts, col_axis, axis=0, tiled=True)
            pre_acc, _ = jax.lax.scan(hop, accx_t, parts_all)
            h8, c8 = _quantized_state_update(pre_acc, c_blk.astype(jnp.int32),
                                             peep32, bias32, sig_lut, tanh_lut)
            h_flat = h8.reshape(B, r_l * t)
            h_full_new = jax.lax.all_gather(h_flat, row_axis, axis=1,
                                            tiled=True)
            # Masked step = identity on the carried codes (pure select, so
            # an all-ones mask is bit-identical to the unmasked chain).
            live = (m > 0)[:, None]
            h_full_new = jnp.where(live, h_full_new, h_full)
            c8 = jnp.where(live[:, :, None], c8, c_blk)
            return (h_full_new, c8), (h_full_new, c8)

        c0 = c0_blk.reshape(B, r_l, t)
        _, (hs, cs) = jax.lax.scan(step, (h0_full, c0), (accx_blk, mask_t))
        return hs, cs.reshape(T, B, r_l * t)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(row_axis), P(row_axis),
                  P(None, None, row_axis), P(None), P(None),
                  P(None, None), P(None, row_axis), P(None, None)),
        out_specs=(P(), P(None, None, row_axis)),
        check_vma=False,
    )
    hs, cs = fn(tiles_h, qp.peep_q, qp.bias_q, acc_x, qp.sig_lut,
                qp.tanh_lut, h0_q, c0_q, mask)
    if not return_state:
        return hs[..., :plan.n_h]
    return hs[..., :plan.n_h], (hs[-1], cs[-1])


# ---------------------------------------------------------------------------
# Staged systolic scale-out of the fused wavefront stack (DESIGN.md §9):
# contiguous layer blocks pinned to the mesh "stage" axis, chunks of the
# hidden-state sequence pipelined stage to stage via ppermute — the paper's
# 3x(5x5) Table-2 topology as ONE dispatch path.
# ---------------------------------------------------------------------------

def stage_layer_blocks(n_layers: int, n_stages: int,
                       blocks: Optional[Sequence[int]] = None
                       ) -> Tuple[Tuple[int, int], ...]:
    """Contiguous layer placement on the stage axis: stage ``s`` owns
    layers ``[lo, hi)``.

    Default (``blocks=None``) is the balanced split: block sizes differ by
    at most one, ceil-sized blocks first — 3 layers on 2 stages place
    layers {0, 1} on stage 0 and {2} on stage 1.  With ``n_stages >
    n_layers`` the TRAILING stages get empty blocks (the ceil-first order
    puts every layer before them); this is the passthrough-delay contract:
    an empty stage hands its input chunk through unchanged and carries no
    state — it adds one macro-step of pipeline delay per empty stage but
    no arithmetic, so trajectories are unchanged (pure schedule).

    ``blocks`` overrides the balanced split with explicit per-stage layer
    COUNTS (the uneven-split geometry the tuner shmoos): it must have
    exactly ``n_stages`` non-negative entries summing to ``n_layers``.
    Any valid split is schedule-only — same per-layer dataflow, same
    chunk handoffs — so uneven splits are bit-equal to the balanced
    default on a fixed (rows, cols) grid.

    Raises ``ValueError`` on non-positive ``n_layers``/``n_stages`` or an
    inconsistent override (silently accepting them used to produce
    nonsense geometry downstream).  Pure geometry; no numerics of its own.
    """
    if n_layers < 1 or n_stages < 1:
        raise ValueError(
            f'stage_layer_blocks needs n_layers >= 1 and n_stages >= 1, '
            f'got n_layers={n_layers}, n_stages={n_stages}')
    if blocks is None:
        base, rem = divmod(n_layers, n_stages)
        sizes = [base + (1 if s_i < rem else 0) for s_i in range(n_stages)]
    else:
        sizes = [int(s) for s in blocks]
        if len(sizes) != n_stages:
            raise ValueError(f'blocks override has {len(sizes)} entries '
                             f'for {n_stages} stages')
        if any(s < 0 for s in sizes):
            raise ValueError(f'blocks override has negative entries: {sizes}')
        if sum(sizes) != n_layers:
            raise ValueError(f'blocks override {sizes} places {sum(sizes)} '
                             f'layers, stack has {n_layers}')
    out, lo = [], 0
    for size in sizes:
        out.append((lo, lo + size))
        lo += size
    return tuple(out)


def _require_staged_axes(mesh: Mesh, stage_axis: str, row_axis: str,
                         col_axis: str) -> Tuple[int, int, int]:
    """Axis check for the staged scale-out: the three named axes must exist
    and every other axis must be 1.  Returns (stages, rows, cols)."""
    names = mesh.axis_names
    for a in (stage_axis, row_axis, col_axis):
        if a not in names:
            raise ValueError(f'mesh axes {names} lack {a!r}')
    if any(mesh.shape[a] > 1 for a in names
           if a not in (stage_axis, row_axis, col_axis)):
        raise ValueError('staged scale-out uses only (stage, row, col) axes')
    return (mesh.shape[stage_axis], mesh.shape[row_axis],
            mesh.shape[col_axis])


def _stage_stack(x: jax.Array, blocks, n_stages: int, lb: int) -> jax.Array:
    """Relayout per-layer arrays (L, ...) into per-stage slots
    (S, Lb, ...), zero-padding slots past each stage's block (their live
    flags mask them to pure passthrough).  Layout only — no arithmetic."""
    out = jnp.zeros((n_stages, lb) + x.shape[1:], x.dtype)
    for s_i, (lo, hi) in enumerate(blocks):
        if hi > lo:
            out = out.at[s_i, :hi - lo].set(x[lo:hi])
    return out


def _stage_live(blocks, n_stages: int, lb: int) -> jax.Array:
    """Per-(stage, slot) liveness flags matching ``_stage_stack``'s
    padding (1.0 = a real layer, 0.0 = a passthrough slot)."""
    live = np.zeros((n_stages, lb), np.float32)
    for s_i, (lo, hi) in enumerate(blocks):
        live[s_i, :hi - lo] = 1.0
    return jnp.asarray(live)


def _stage_of(blocks, layer: int) -> Tuple[int, int]:
    """(stage index, slot index) of a global layer under ``blocks``."""
    for s_i, (lo, hi) in enumerate(blocks):
        if lo <= layer < hi:
            return s_i, layer - lo
    raise ValueError(f'layer {layer} outside {blocks}')


def _staged_schedule(n_layers: int, T: int, n_stages: int,
                     chunk: Optional[int],
                     blocks: Optional[Sequence[int]] = None):
    """The one source of the staged pipeline geometry, shared by the f32
    and int8 wrappers so their schedules (and hence the cross-engine state
    handoff) cannot desynchronize: chunk default ``ceil(T / (4*stages))``
    (fill/drain stays under ~1/4 of macro-steps; chunk=1 is the paper's
    frame-by-frame handover), ``K`` chunks padding T to ``T_p``, ``M = K +
    S - 1`` macro-steps, the contiguous layer blocks (balanced, or the
    explicit per-stage counts of an uneven split — schedule-only either
    way) and the slot count.  Returns (Tc, K, T_p, M, blocks, Lb)."""
    if chunk is None:
        chunk = max(1, -(-T // (4 * n_stages)))
    Tc = min(int(chunk), T)
    K = -(-T // Tc)
    blocks = stage_layer_blocks(n_layers, n_stages, blocks)
    Lb = max(1, max(hi - lo for lo, hi in blocks))
    return Tc, K, K * Tc, K + n_stages - 1, blocks, Lb


#: Legal in-stage schedules for the staged backend: ``'batched'`` walks the
#: chunk's (slot, step) grid diagonal-major — one slot-batched dot per
#: diagonal, ``Tc + Lb - 1`` rounds per macro-step — while ``'sequential'``
#: (the PR 5 dataflow) runs the layer block slot by slot, ``Lb * Tc`` rounds.
#: Both orders produce bit-equal f32 / bit-identical int8 trajectories; the
#: choice is schedule-only.
IN_STAGE_MODES = ('batched', 'sequential')


def resolve_staged_chunk(n_layers: int, T: int, n_stages: int, *,
                         n_h: int = 0, n_x: int = 0, batch: int = 0,
                         mesh: Optional[Mesh] = None,
                         kind: str = 'stack_f32') -> int:
    """Chunk depth ``Tc`` the staged wrappers will use when the caller
    passes ``chunk=None``: a measured winner from the installed schedule
    cache (``repro.tune``) when one matches this ``(shape, mesh)``, else
    the hand-derived ``_staged_schedule`` default ``ceil(T / (4*stages))``.
    Selection only — the returned depth changes the pipeline schedule, not
    the numerics (chunked and monolithic trajectories are bit-equal, see
    ``systolic_lstm_stack_seq``)."""
    from ..tune.schedule import current_schedule_cache, mesh_signature
    cache = current_schedule_cache()
    if cache is not None:
        ent = cache.lookup(kind, n_x=n_x, n_h=n_h, n_layers=n_layers,
                           T=T, B=batch, mesh=mesh_signature(mesh))
        if ent is not None and ent.tc:
            return min(int(ent.tc), T)
    return _staged_schedule(n_layers, T, n_stages, None)[0]


def resolve_staged_in_stage(n_layers: int, T: int, n_stages: int, *,
                            n_h: int = 0, n_x: int = 0, batch: int = 0,
                            mesh: Optional[Mesh] = None,
                            kind: str = 'stack_f32') -> str:
    """In-stage round order the staged wrappers use when the caller passes
    ``in_stage=None``: the measured winner from the installed schedule
    cache for this ``(shape, mesh)`` when one exists, else ``'batched'``
    (the ``Tc + Lb - 1``-round diagonal order — the silicon's dataflow).
    Selection only: both orders are bit-equal f32 / bit-identical int8
    (``IN_STAGE_MODES``), so the cache can only pick among proven-
    identical schedules.  The measured choice matters because the orders
    optimise for different hosts: batched wins where stages' slots truly
    run concurrently (real multi-core / the silicon), sequential's hoisted
    wide below-GEMMs win on FLOP-bound single-core emulation."""
    from ..tune.schedule import current_schedule_cache, mesh_signature
    cache = current_schedule_cache()
    if cache is not None:
        ent = cache.lookup(kind, n_x=n_x, n_h=n_h, n_layers=n_layers,
                           T=T, B=batch, mesh=mesh_signature(mesh))
        if ent is not None and ent.in_stage in IN_STAGE_MODES:
            return ent.in_stage
    return 'batched'


def resolve_staged_blocks(n_layers: int, T: int, n_stages: int, *,
                          n_h: int = 0, n_x: int = 0, batch: int = 0,
                          mesh: Optional[Mesh] = None,
                          kind: str = 'stack_f32'
                          ) -> Optional[Tuple[int, ...]]:
    """Per-stage layer COUNTS the staged wrappers use when the caller
    passes ``blocks=None``: the tuned uneven split from the installed
    schedule cache for this ``(shape, mesh)`` when one exists (the
    geometry tuner's ``blocks='2,1'``-style field), else None (the
    balanced ``stage_layer_blocks`` default).  Selection only — any valid
    split runs the same per-layer dataflow on the same (rows, cols) grid,
    so splits are bit-equal schedules (tests/test_geometry_tune.py).
    A cached split that does not fit THIS call (wrong stage count, wrong
    layer total, negative entries) is ignored, never trusted: the
    structural guards stay authoritative over the cache."""
    from ..tune.schedule import current_schedule_cache, mesh_signature
    cache = current_schedule_cache()
    if cache is None:
        return None
    ent = cache.lookup(kind, n_x=n_x, n_h=n_h, n_layers=n_layers,
                       T=T, B=batch, mesh=mesh_signature(mesh))
    if ent is None or not getattr(ent, 'blocks', ''):
        return None
    try:
        counts = tuple(int(p) for p in str(ent.blocks).split(','))
    except ValueError:
        return None
    if (len(counts) != n_stages or any(c < 0 for c in counts)
            or sum(counts) != n_layers):
        return None
    return counts


def _staged_forward(static, w_in, w_h, peep, b, pre_x, h0s, c0s, mask=None):
    """Staged distributed whole-stack forward (padded in, un-padded out).

    Numerics contract: allclose to the layerwise composition (chaining
    ``core.lstm.lstm_layer`` / the §8 fused stack) — inside a stage each
    layer of the block runs the §6 per-step dataflow (resident ``bn x bk``
    recurrent block, per-step ``psum`` over ``col``, ``all_gather`` of the
    h chunks over ``row``) over one Tc-step chunk at a time, the chunk's
    below-layer input stream hoisted into one wide matmul; chunks pipeline
    across stages via ``ppermute`` — at macro-step m, stage s computes
    chunk ``m - s`` while stage s+1 consumes chunk ``m - s - 1`` — so
    inter-stage activations never fan through a host gather.  ``mask``:
    optional (T, B) validity mask; a masked step is identity on every
    layer's carried state via ``jnp.where`` (pure select, so ``None`` and
    an all-ones mask are bit-identical).  Returns (hs, cs), each
    (L, T, B, n_h) — the full trajectories feed the cross-layer VJP and
    the chunked serving carry.

    ``static[5]`` selects the in-stage schedule (``IN_STAGE_MODES``):
    ``'sequential'`` runs the stage's layer block slot by slot over the
    chunk (``Lb * Tc`` collective rounds per macro-step); ``'batched'``
    walks the same (slot, step) grid diagonal-major like the §8 stack
    kernel — slot i executes step ``d - i`` at diagonal d, all live slots
    in ONE ``(Lb, B, bk) x (Lb, bk, 4*bn)`` dot per diagonal, ``Tc + Lb -
    1`` rounds — with identical per-element arithmetic and addition order
    (separate own/below psums, ``pre = psum(own) + (psum(below) +
    pre_x)``), so the two orders are bit-equal.

    ``static[6]`` (optional, ``None`` = balanced) carries the per-stage
    layer counts of an uneven stage split (``stage_layer_blocks``'
    ``blocks`` override) — schedule-only like the in-stage order.
    """
    mesh, stage_axis, row_axis, col_axis, chunk, in_stage = static[:6]
    split = static[6] if len(static) > 6 else None
    assert in_stage in IN_STAGE_MODES, in_stage
    T, B, _, n_h = pre_x.shape
    L = w_h.shape[0]
    S, mr, mc = (mesh.shape[stage_axis], mesh.shape[row_axis],
                 mesh.shape[col_axis])
    n_h_p, bn, bk = _scaleout_blocks(n_h, mr, mc)
    pad = n_h_p - n_h
    Tc, K, T_p, M, blocks, Lb = _staged_schedule(L, T, S, chunk, split)

    if mask is None:
        mask = jnp.ones((T, B), jnp.bool_)
    mask_k = jnp.zeros((T_p, B), jnp.bool_).at[:T].set(mask).reshape(K, Tc, B)
    pre_p = jnp.pad(pre_x, ((0, T_p - T), (0, 0), (0, 0), (0, pad))
                    ).reshape(K, Tc, B, GATES, n_h_p)

    pad_w = ((0, 0), (0, 0), (0, pad), (0, pad))
    w_in_s = _stage_stack(jnp.pad(w_in, pad_w), blocks, S, Lb)
    w_h_s = _stage_stack(jnp.pad(w_h, pad_w), blocks, S, Lb)
    peep_s = _stage_stack(jnp.pad(peep, ((0, 0), (0, 0), (0, pad))),
                          blocks, S, Lb)
    bias_s = _stage_stack(jnp.pad(b, ((0, 0), (0, 0), (0, pad))),
                          blocks, S, Lb)
    h0_s = _stage_stack(jnp.pad(h0s, ((0, 0), (0, 0), (0, pad))),
                        blocks, S, Lb)
    c0_s = _stage_stack(jnp.pad(c0s, ((0, 0), (0, 0), (0, pad))),
                        blocks, S, Lb)
    live = _stage_live(blocks, S, Lb)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    def body(w_in_l, w_h_l, peep_l, bias_l, h0_l, c0_l, live_l, pre_l,
             mask_l):
        """SPMD body on device (s, r, c).

        w_in_l/w_h_l: (1, Lb, 4, bn, bk) — the stage's resident layer
        block, tile-stationary for the whole utterance; pre_l:
        (K, Tc, B, 4, bn) hoisted layer-0 stream (consumed by stage 0
        only); mask_l: (K, Tc, B) replicated validity chunks.
        """
        s_idx = jax.lax.axis_index(stage_axis)
        col = jax.lax.axis_index(col_axis)
        w_in_l, w_h_l = w_in_l[0], w_h_l[0]
        peep_l, bias_l, live_l = peep_l[0], bias_l[0], live_l[0]

        def layer_chunk(w4, peep4, bias4, pre_stream, h0f, c0b, m_chunk):
            # One slot's Tc-step scan — exactly the §6 step dataflow.
            def step(carry_i, inp):
                h_full, c = carry_i
                pre_t, m = inp
                h_k = jax.lax.dynamic_slice(h_full, (0, col * bk), (B, bk))
                part = jnp.einsum('gnk,bk->bgn', w4, h_k)
                pre = jax.lax.psum(part, col_axis) + pre_t
                i = jax.nn.sigmoid(pre[:, I] + peep4[PEEP_I] * c + bias4[I])
                f = jax.nn.sigmoid(pre[:, F] + peep4[PEEP_F] * c + bias4[F])
                g = jnp.tanh(pre[:, G] + bias4[G])
                c_new = f * c + i * g
                o = jax.nn.sigmoid(pre[:, O] + peep4[PEEP_O] * c_new
                                   + bias4[O])
                h_new = o * jnp.tanh(c_new)
                h_full_new = jax.lax.all_gather(h_new, row_axis, axis=1,
                                                tiled=True)
                # Masked step = identity on the carried state (pure select).
                keep = m[:, None]
                h_full_new = jnp.where(keep, h_full_new, h_full)
                c_new = jnp.where(keep, c_new, c)
                return (h_full_new, c_new), (h_full_new, c_new)

            (h_T, c_T), (hs_c, cs_c) = jax.lax.scan(
                step, (h0f, c0b), (pre_stream, m_chunk))
            return hs_c, cs_c, h_T, c_T

        def macro(carry_m, m_idx):
            h_state, c_state, out_prev = carry_m
            k = m_idx - s_idx
            act = (k >= 0) & (k < K)
            kc = jnp.clip(k, 0, K - 1)
            # Inter-stage handover: stage s-1's chunk from macro-step m-1.
            handed = (out_prev if S == 1 else
                      jax.lax.ppermute(out_prev, stage_axis, fwd_perm))
            pre_chunk = jax.lax.dynamic_index_in_dim(pre_l, kc, 0,
                                                     keepdims=False)
            m_chunk = jax.lax.dynamic_index_in_dim(mask_l, kc, 0,
                                                   keepdims=False) & act
            below = handed
            hs_slots, cs_slots, new_h, new_c = [], [], [], []
            for i in range(Lb):
                def run_slot(ops, i=i):
                    below_i, h0f, c0b = ops
                    # Chunk-hoisted input stream: this slot's W_in block
                    # MACs the below trajectory, partials meeting in a psum
                    # over "col" — one wide matmul per chunk instead of
                    # per step.
                    below_k = jax.lax.dynamic_slice(
                        below_i, (0, 0, col * bk), (Tc, B, bk))
                    pre_stream = jax.lax.psum(
                        jnp.einsum('gnk,tbk->tbgn', w_in_l[i], below_k),
                        col_axis)
                    if i == 0:
                        # Stage 0's first slot streams the hoisted pre_x
                        # (its W_in block is zero, so the handed term
                        # vanishes).
                        pre_stream = pre_stream + jnp.where(s_idx == 0,
                                                            pre_chunk, 0.0)
                    return layer_chunk(w_h_l[i], peep_l[i], bias_l[i],
                                       pre_stream, h0f, c0b, m_chunk)

                def skip_slot(ops):
                    # Fill/drain bubble or passthrough slot: hand the input
                    # straight through, carry state untouched, no compute.
                    # The emitted trajectory entries of a skipped macro-step
                    # are never gathered (collection takes m = k + s only).
                    below_i, h0f, c0b = ops
                    return (below_i, jnp.zeros((Tc, B, bn), below_i.dtype),
                            h0f, c0b)

                # The predicate is uniform across the stage's (row, col)
                # group — `act` depends only on the stage index and
                # `live` is per-stage data — so the collectives inside the
                # taken branch always match up within their groups.
                hs_c, cs_c, h_T, c_T = jax.lax.cond(
                    act & (live_l[i] > 0), run_slot, skip_slot,
                    (below, h_state[i], c_state[i]))
                below = hs_c
                hs_slots.append(hs_c)
                cs_slots.append(cs_c)
                new_h.append(h_T)
                new_c.append(c_T)
            return ((jnp.stack(new_h), jnp.stack(new_c), below),
                    (jnp.stack(hs_slots), jnp.stack(cs_slots)))

        nl = jnp.sum((live_l > 0).astype(jnp.int32))
        # Per-stage live-slot counts are static data (``blocks``), so the
        # batched macro dispatches each stage — via a stage-uniform switch —
        # to a branch specialized to its own count: single-layer stages
        # reuse the sequential chunk scan verbatim (zero dead-slot work),
        # and cnt-layer stages walk Tc + cnt - 1 diagonals with ONE fused
        # slot-batched dot and ONE psum per diagonal.
        counts = sorted({hi - lo for lo, hi in blocks if hi > lo})

        def macro_batched(carry_m, m_idx):
            # Same chunk pipeline as `macro`, but each stage's (slot, step)
            # grid is walked diagonal-major: at diagonal d, slot i executes
            # its step t = d - i (out-of-window diagonals are
            # select-identity bubbles).  Slot i's below input at diagonal d
            # is slot i-1's carried post-step h from diagonal d-1 — exactly
            # its step-t output — so the state stack itself is the
            # diagonal-major inter-layer buffer.
            h_state, c_state, out_prev = carry_m
            k = m_idx - s_idx
            act = (k >= 0) & (k < K)
            kc = jnp.clip(k, 0, K - 1)
            handed = (out_prev if S == 1 else
                      jax.lax.ppermute(out_prev, stage_axis, fwd_perm))
            pre_chunk = jax.lax.dynamic_index_in_dim(pre_l, kc, 0,
                                                     keepdims=False)
            m_chunk = jax.lax.dynamic_index_in_dim(mask_l, kc, 0,
                                                   keepdims=False) & act

            def hoist_stream0(handed_c):
                # Slot 0's below stream (the handed chunk) is fully known
                # at macro start: hoist its W_in MAC into ONE wide matmul +
                # psum — the very ops (and addition association) of the
                # sequential slot loop.
                handed_k = jax.lax.dynamic_slice(
                    handed_c, (0, 0, col * bk), (Tc, B, bk))
                pre_stream0 = jax.lax.psum(
                    jnp.einsum('gnk,tbk->tbgn', w_in_l[0], handed_k),
                    col_axis)
                return pre_stream0 + jnp.where(s_idx == 0, pre_chunk, 0.0)

            def run_single(ops):
                # cnt == 1 stage: exactly the sequential single-slot chunk
                # scan — Tc one-slot rounds, nothing batched, no dead-slot
                # compute on the padding slots.
                handed_c, h0_all, c0_all = ops
                hs_c, cs_c, h_T0, c_T0 = layer_chunk(
                    w_h_l[0], peep_l[0], bias_l[0],
                    hoist_stream0(handed_c), h0_all[0], c0_all[0], m_chunk)
                h_T = jnp.concatenate([h_T0[None], h0_all[1:]], axis=0)
                c_T = jnp.concatenate([c_T0[None], c0_all[1:]], axis=0)
                pad_h = jnp.zeros((Lb - 1, Tc, B, n_h_p), hs_c.dtype)
                pad_c = jnp.zeros((Lb - 1, Tc, B, bn), cs_c.dtype)
                return (h_T, c_T, hs_c,
                        jnp.concatenate([hs_c[None], pad_h], axis=0),
                        jnp.concatenate([cs_c[None], pad_c], axis=0))

            def make_run(cnt):
                def run_cnt(ops):
                    handed_c, h0_all, c0_all = ops
                    pre_stream0 = hoist_stream0(handed_c)
                    D = Tc + cnt - 1
                    # Diagonal -> (slot, step) geometry and validity masks
                    # are index arithmetic on the schedule: precompute them
                    # (and the slot-0 stream replay) once per macro-step and
                    # feed the diagonal scan through its xs.
                    t_idx = (jnp.arange(D)[:, None]
                             - jnp.arange(cnt)[None, :])
                    valid = (t_idx >= 0) & (t_idx < Tc)
                    t_clip = jnp.clip(t_idx, 0, Tc - 1)
                    pre0_d = pre_stream0[jnp.clip(jnp.arange(D), 0, Tc - 1)]
                    keep_d = (jnp.take(m_chunk, t_clip, axis=0)
                              & valid[..., None])
                    # Own-h and below dots fuse into ONE slot-batched
                    # einsum + ONE psum: the weight stack [W_h | W_in[1:]]
                    # is loop-invariant, and a psum of concatenated
                    # operands is elementwise — splitting the result back
                    # recovers psum(own) and psum(below) bit for bit, so
                    # the addition association stays psum(own) +
                    # (psum(below) + pre_x), matching the sequential loop.
                    w_cat = jnp.concatenate([w_h_l[:cnt], w_in_l[1:cnt]],
                                            axis=0)
                    peep_c, bias_c = peep_l[:cnt], bias_l[:cnt]

                    def diag(carry_d, xs_d):
                        h_all, c_all = carry_d
                        pre0_t, keep_t = xs_d
                        h_k = jax.lax.dynamic_slice(
                            h_all, (0, 0, col * bk), (cnt, B, bk))
                        # Slot i>=1 reads slot i-1's post-step h — the same
                        # col slice just taken for the own-h dot.
                        in_cat = jnp.concatenate([h_k, h_k[:-1]], axis=0)
                        part = jnp.einsum('lgnk,lbk->lbgn', w_cat, in_cat)
                        psummed = jax.lax.psum(part, col_axis)
                        pre = psummed[:cnt] + jnp.concatenate(
                            [pre0_t[None], psummed[cnt:]], axis=0)
                        c = c_all
                        i = jax.nn.sigmoid(pre[:, :, I]
                                           + peep_c[:, PEEP_I][:, None] * c
                                           + bias_c[:, I][:, None])
                        f = jax.nn.sigmoid(pre[:, :, F]
                                           + peep_c[:, PEEP_F][:, None] * c
                                           + bias_c[:, F][:, None])
                        g = jnp.tanh(pre[:, :, G] + bias_c[:, G][:, None])
                        c_new = f * c + i * g
                        o = jax.nn.sigmoid(pre[:, :, O]
                                           + peep_c[:, PEEP_O][:, None]
                                           * c_new
                                           + bias_c[:, O][:, None])
                        h_new = o * jnp.tanh(c_new)
                        h_full_new = jax.lax.all_gather(
                            h_new, row_axis, axis=2, tiled=True)
                        keep = keep_t[:, :, None]
                        h_next = jnp.where(keep, h_full_new, h_all)
                        c_next = jnp.where(keep, c_new, c_all)
                        return (h_next, c_next), (h_next, c_next)

                    (h_Tc, c_Tc), (hs_d, cs_d) = jax.lax.scan(
                        diag, (h0_all[:cnt], c0_all[:cnt]),
                        (pre0_d, keep_d))
                    # Diagonal emissions (D, cnt, ...) -> the sequential
                    # layout (cnt, Tc, ...): slot i's step t is at d = i+t.
                    hs_sl = jnp.stack([hs_d[i:i + Tc, i]
                                       for i in range(cnt)])
                    cs_sl = jnp.stack([cs_d[i:i + Tc, i]
                                       for i in range(cnt)])
                    out = hs_sl[cnt - 1]
                    h_T = jnp.concatenate([h_Tc, h0_all[cnt:]], axis=0)
                    c_T = jnp.concatenate([c_Tc, c0_all[cnt:]], axis=0)
                    hs_sl = jnp.concatenate(
                        [hs_sl, jnp.zeros((Lb - cnt, Tc, B, n_h_p),
                                          hs_d.dtype)], axis=0)
                    cs_sl = jnp.concatenate(
                        [cs_sl, jnp.zeros((Lb - cnt, Tc, B, bn),
                                          cs_d.dtype)], axis=0)
                    return h_T, c_T, out, hs_sl, cs_sl
                return run_cnt

            def skip_macro(ops):
                # Fill/drain macro-step (or empty stage): passthrough +
                # untouched state, no compute; the zero emissions are never
                # gathered.
                handed_c, h0_all, c0_all = ops
                return (h0_all, c0_all, handed_c,
                        jnp.zeros((Lb, Tc, B, n_h_p), handed_c.dtype),
                        jnp.zeros((Lb, Tc, B, bn), handed_c.dtype))

            # The branch index depends only on s_idx/m_idx and per-stage
            # data (nl), so every device of a stage's (row, col) collective
            # groups takes the same branch and the collectives inside it
            # match up within their groups.
            branches = [skip_macro] + [
                (run_single if c == 1 else make_run(c)) for c in counts]
            idx = sum(((nl > c).astype(jnp.int32) for c in counts),
                      jnp.int32(0))
            branch = jnp.where(act & (nl > 0), 1 + idx, 0)
            h_T, c_T, out, hs_sl, cs_sl = jax.lax.switch(
                branch, branches, (handed, h_state, c_state))
            return (h_T, c_T, out), (hs_sl, cs_sl)

        macro_fn = (macro_batched
                    if in_stage == 'batched' and Lb > 1 else macro)
        out0 = jnp.zeros((Tc, B, n_h_p), pre_l.dtype)
        _, (hs_all, cs_all) = jax.lax.scan(
            macro_fn, (h0_l[0], c0_l[0], out0), jnp.arange(M))
        return hs_all, cs_all

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(stage_axis, None, None, row_axis, col_axis),
                  P(stage_axis, None, None, row_axis, col_axis),
                  P(stage_axis, None, None, row_axis),
                  P(stage_axis, None, None, row_axis),
                  P(stage_axis, None, None, None),
                  P(stage_axis, None, None, row_axis),
                  P(stage_axis, None),
                  P(None, None, None, None, row_axis),
                  P(None, None, None)),
        out_specs=(P(None, stage_axis, None, None, None),
                   P(None, stage_axis, None, None, row_axis)),
        check_vma=False,
    )
    hs_g, cs_g = fn(w_in_s, w_h_s, peep_s, bias_s, h0_s, c0_s, live,
                    pre_p, mask_k)
    hs_g = hs_g.reshape(M, S, Lb, Tc, B, n_h_p)
    cs_g = cs_g.reshape(M, S, Lb, Tc, B, n_h_p)

    def layer_traj(g, layer):
        # Stage s emits chunk k at macro-step k + s: a pure re-indexing.
        s_i, slot = _stage_of(blocks, layer)
        return g[s_i:s_i + K, s_i, slot].reshape(T_p, B, n_h_p)[:T, :, :n_h]

    hs = jnp.stack([layer_traj(hs_g, l) for l in range(L)])
    cs = jnp.stack([layer_traj(cs_g, l) for l in range(L)])
    return hs, cs


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def systolic_stack_seq_fused(static, w_in, w_h, peep, b, pre_x, h0s, c0s):
    """Staged distributed whole-stack LSTM with the production training VJP.

    Same contract as ``kernels.lstm_seq.stack_ops.lstm_stack_seq_fused``
    (forward allclose to looping ``lstm_scan_fused`` over the layers;
    backward composes the cross-layer gate recompute across stage
    boundaries via the shared ``core.lstm.lstm_stack_bwd_recompute_gates``
    — the saved trajectories are already stage-gathered, so the backward
    is numerically identical to the single-engine fused stack's), but the
    forward runs stage-pipelined on the ``static = (mesh, stage_axis,
    row_axis, col_axis, chunk, in_stage[, blocks])`` grid.  The in-stage
    schedule (``IN_STAGE_MODES``) and the optional uneven stage split
    change only the round order / layer placement, not the trajectories,
    so gradients are bit-equal across schedules too.
    """
    hs, cs = _staged_forward(static, w_in, w_h, peep, b, pre_x, h0s, c0s)
    return hs[-1], (hs[:, -1], cs[:, -1])


def _ssf_fwd(static, w_in, w_h, peep, b, pre_x, h0s, c0s):
    hs, cs = _staged_forward(static, w_in, w_h, peep, b, pre_x, h0s, c0s)
    return ((hs[-1], (hs[:, -1], cs[:, -1])),
            (w_in, w_h, peep, b, pre_x, hs, cs, h0s, c0s))


def _ssf_bwd(static, res, grads):
    from .lstm import lstm_stack_bwd_recompute_gates
    w_in, w_h, peep, b, pre_x, hs, cs, h0s, c0s = res
    return lstm_stack_bwd_recompute_gates(w_in, w_h, peep, b, pre_x, hs, cs,
                                          h0s, c0s, grads)


systolic_stack_seq_fused.defvjp(_ssf_fwd, _ssf_bwd)


def systolic_lstm_stack_seq(params, mesh: Optional[Mesh], xs: jax.Array,
                            states=None, *,
                            valid_len: Optional[jax.Array] = None,
                            chunk: Optional[int] = None,
                            in_stage: Optional[str] = None,
                            blocks: Optional[Sequence[int]] = None,
                            stage_axis: str = 'stage',
                            row_axis: str = 'row', col_axis: str = 'col'
                            ) -> Tuple[jax.Array, Tuple]:
    """Staged scale-out of the fused wavefront stack — the
    ``pallas_seq_fused_systolic`` backend (DESIGN.md §9).

    Drop-in for the layer loop of ``core.lstm.lstm_stack_apply`` /
    ``lstm_stack_chunk`` (same signature family as
    ``kernels.lstm_seq.lstm_stack_seq``): each stage of the installed
    ``(stage, row, col)`` mesh holds ONE contiguous layer block
    weight-stationary (``stage_layer_blocks``; the paper's 3x(5x5) places
    one layer per 5x5 stage) and runs the fused-stack composition over it
    with the §6 row/col tile-stationary dataflow, while the hidden-state
    sequence pipelines across stages in ``chunk``-step slices handed over
    by ``ppermute`` — stage s computes chunk k while stage s+1 consumes
    chunk k-1, so a T-step utterance costs ``ceil(T/chunk) + S - 1``
    macro-steps of the bottleneck stage instead of every stage in
    sequence.

    Output allclose to the layerwise composition (``lstm_stack_apply`` on
    any backend); differentiable via the cross-layer gate-recompute VJP
    (``systolic_stack_seq_fused``).  ``valid_len`` follows the §7 masking
    contract (masked steps are identity on every layer's carried state;
    inference-only), and ``states`` carries the per-layer ``(h, c)`` for
    chunked serving.  A ``None`` or all-1 mesh degenerates to the
    single-engine §8 kernel (``lstm_stack_seq``) — the composition this
    function scales out.  ``chunk`` defaults to the installed schedule
    cache's measured winner for this (shape, mesh) when one exists
    (``resolve_staged_chunk``), else ``ceil(T / (4*stages))`` (fill/drain
    stays under ~1/4 of macro-steps; chunk=1 is the paper's frame-by-frame
    handover).  ``in_stage`` picks the in-stage round order
    (``IN_STAGE_MODES``): ``'batched'`` executes each stage's layer block
    diagonal-major — all live slots advance in one slot-batched dot per
    diagonal, ``Tc + Lb - 1`` rounds per macro-step instead of ``Lb * Tc``
    — and is bit-equal to ``'sequential'`` (the PR 5 slot loop), which
    remains as the measured baseline; ``None`` (default) takes the
    schedule cache's measured winner for this (shape, mesh), else
    ``'batched'`` (``resolve_staged_in_stage``).  ``blocks`` (per-stage
    layer counts) overrides ``stage_layer_blocks``' balanced split with a
    tuned uneven one; ``None`` takes the schedule cache's winner for this
    (shape, mesh) when one exists (``resolve_staged_blocks``), else the
    balanced default — any valid split is a bit-equal schedule on a fixed
    (rows, cols) grid.
    """
    from ..kernels.lstm_seq import lstm_stack_seq, stack_fused_compatible
    assert stack_fused_compatible(params), \
        'staged scale-out needs homogeneous hidden widths'
    assert xs.ndim == 3, 'systolic_lstm_stack_seq expects (T, B, N_x) input'
    if mesh is None or all(sz == 1 for sz in mesh.shape.values()):
        return lstm_stack_seq(params, xs, states, valid_len=valid_len)
    S, _, _ = _require_staged_axes(mesh, stage_axis, row_axis, col_axis)
    layers = params.layers
    n_h = layers[0].n_h
    T, B = xs.shape[0], xs.shape[1]
    if chunk is None:
        chunk = resolve_staged_chunk(len(layers), T, S, n_h=n_h,
                                     n_x=layers[0].n_x, batch=B, mesh=mesh)
    if in_stage is None:
        in_stage = resolve_staged_in_stage(len(layers), T, S, n_h=n_h,
                                           n_x=layers[0].n_x, batch=B,
                                           mesh=mesh)
    if blocks is None:
        blocks = resolve_staged_blocks(len(layers), T, S, n_h=n_h,
                                       n_x=layers[0].n_x, batch=B,
                                       mesh=mesh)
    split = tuple(int(s) for s in blocks) if blocks is not None else None
    Tc = _staged_schedule(len(layers), T, S, chunk, split)[0]

    from ..kernels.lstm_seq.stack_ops import _stack_arrays
    from .lstm import stack_carry_arrays
    w_in, w_h, peep, b = _stack_arrays(params)
    pre_x = jnp.einsum('ghx,tbx->tbgh', layers[0].w_x, xs)    # hoisted

    h0s, c0s = stack_carry_arrays(states, len(layers), B, n_h, xs.dtype)
    static = (mesh, stage_axis, row_axis, col_axis, Tc, in_stage, split)
    if valid_len is not None:
        from .lstm import valid_len_mask
        mask = valid_len_mask(T, valid_len, B)
        hs, cs = _staged_forward(static, w_in, w_h, peep, b, pre_x, h0s,
                                 c0s, mask)
        ys, h_T, c_T = hs[-1], hs[:, -1], cs[:, -1]
    else:
        ys, (h_T, c_T) = systolic_stack_seq_fused(static, w_in, w_h, peep,
                                                  b, pre_x, h0s, c0s)
    finals = tuple((h_T[l], c_T[l]) for l in range(len(layers)))
    return ys, finals


def systolic_lstm_stack_seq_quantized(qps, mesh: Optional[Mesh],
                                      xs_q: jax.Array, *,
                                      state=None,
                                      valid_len: Optional[jax.Array] = None,
                                      return_state: bool = False,
                                      chunk: Optional[int] = None,
                                      in_stage: Optional[str] = None,
                                      blocks: Optional[Sequence[int]] = None,
                                      stage_axis: str = 'stage',
                                      row_axis: str = 'row',
                                      col_axis: str = 'col'):
    """Staged distributed int8 stack, bit-identical to the silicon chain.

    The int8 form of ``systolic_lstm_stack_seq``: same stage placement and
    chunk pipelining, but every step replays the engine-order saturating
    datapath — each layer's below/x-region prefix of the hop chain is
    h-independent within the chunk and folds through the shared
    ``_x_prefix_fold`` (layer 0's whole-sequence prefix comes from
    ``quantized_x_prefix``, exactly as in §6/§8), the own-h region tile
    partials are ``all_gather``ed over ``col`` and hopped sequentially in
    engine order, and the elementwise tail is the shared
    ``_quantized_state_update``.  Output is therefore **bit-identical** to
    chaining the single-engine fused stack
    (``kernels.lstm_seq.lstm_stack_seq_quantized``) — and hence to
    chaining ``lstm_layer_seq_quantized`` / the reference
    ``systolic_cell_quantized`` scan — per layer block.

    qps: per-layer quantized packs (one tile, one hidden width, inner
    ``n_x == n_h``); xs_q: (T, B, n_x) int8 codes.  ``state`` /
    ``valid_len`` / ``return_state`` follow the §7 chunk-carry contract of
    ``lstm_stack_seq_quantized`` verbatim (opaque per-layer
    ``(h_q, c_q)`` codes, each (L, B, padded_h); masked steps are pure
    selects on the carried codes), so the staged mesh, the single-engine
    fused stack and the streaming engine can hand state to each other
    mid-sequence.  ``in_stage`` follows ``IN_STAGE_MODES`` (``None`` =
    the schedule cache's winner, else ``'batched'``, as in
    ``resolve_staged_in_stage``): the ``'batched'`` order advances every
    live slot of the stage's block per
    in-chunk diagonal (the below/x prefix folds through a slot-vmapped
    ``_x_prefix_fold``, the own-h hops replay in the same engine order),
    so the integer datapath — and hence the emitted codes — is unchanged
    from ``'sequential'`` op for op.  Requires ``plan.rows % mesh rows ==
    0`` and
    ``plan.cols_h % mesh cols == 0``; a ``None``/all-1 mesh degenerates to
    the single-engine fused stack.
    """
    from ..kernels.lstm_seq import lstm_stack_seq_quantized
    if mesh is None or all(sz == 1 for sz in mesh.shape.values()):
        return lstm_stack_seq_quantized(qps, xs_q, state=state,
                                        valid_len=valid_len,
                                        return_state=return_state)
    plans = [qp.plan for qp in qps]
    p0 = plans[0]
    L = len(qps)
    assert L >= 1
    assert all(p.tile == p0.tile for p in plans), 'mixed tiles'
    assert all(p.n_h == p0.n_h for p in plans), 'mixed hidden widths'
    assert all(p.n_x == p0.n_h for p in plans[1:]), \
        'inner layers must consume the stack hidden width'
    S, mr, mc = _require_staged_axes(mesh, stage_axis, row_axis, col_axis)
    t, R, c_h, padded_h = p0.tile, p0.rows, p0.cols_h, p0.padded_h
    if R % mr or c_h % mc:
        raise ValueError(f'engine grid {R}x{c_h} (h-region) does not divide '
                         f'mesh {mr}x{mc}')
    r_l, c_l = R // mr, c_h // mc
    assert xs_q.ndim == 3, \
        'systolic_lstm_stack_seq_quantized expects (T, B, n_x)'
    T, B = xs_q.shape[0], xs_q.shape[1]
    if chunk is None:
        chunk = resolve_staged_chunk(L, T, S, n_h=p0.n_h, n_x=p0.n_x,
                                     batch=B, mesh=mesh, kind='stack_int8')
    if in_stage is None:
        in_stage = resolve_staged_in_stage(L, T, S, n_h=p0.n_h, n_x=p0.n_x,
                                           batch=B, mesh=mesh,
                                           kind='stack_int8')
    assert in_stage in IN_STAGE_MODES, in_stage
    if blocks is None:
        blocks = resolve_staged_blocks(L, T, S, n_h=p0.n_h, n_x=p0.n_x,
                                       batch=B, mesh=mesh,
                                       kind='stack_int8')
    Tc, K, T_p, M, blocks, Lb = _staged_schedule(L, T, S, chunk, blocks)

    # Resident weights: own-h region tiles sharded (row, col); below/x
    # region tiles row-sharded (each row device folds its own prefix).
    # Layer 0's below slot is zero — its whole-sequence x prefix is
    # hoisted host-side through the one shared implementation.
    own_s = _stage_stack(
        jnp.stack([qp.tiles_q[:, p.cols_x:] for qp, p in zip(qps, plans)]),
        blocks, S, Lb)
    below_all = [jnp.zeros((R, c_h, GATES, t, t), jnp.int8)]
    for qp, p in zip(qps[1:], plans[1:]):
        below_all.append(qp.tiles_q[:, :p.cols_x])
    below_s = _stage_stack(jnp.stack(below_all), blocks, S, Lb)
    peep_s = _stage_stack(jnp.stack([qp.peep_q for qp in qps]), blocks, S, Lb)
    bias_s = _stage_stack(jnp.stack([qp.bias_q for qp in qps]), blocks, S, Lb)

    xs_flat = jnp.zeros((T_p, B, p0.n_x), jnp.int8).at[:T].set(xs_q)
    acc_x = quantized_x_prefix(qps[0], xs_flat).reshape(K, Tc, B, R, GATES, t)

    if state is None:
        h0 = jnp.zeros((L, B, padded_h), jnp.int8)
        c0 = jnp.zeros((L, B, padded_h), jnp.int8)
    else:
        h0 = state[0].reshape(L, B, padded_h)
        c0 = state[1].reshape(L, B, padded_h)
    h0_s = _stage_stack(h0, blocks, S, Lb)
    c0_s = _stage_stack(c0.reshape(L, B, R, t), blocks, S, Lb)
    if valid_len is None:
        mask = jnp.ones((T, B), jnp.int8)
    else:
        from .lstm import valid_len_mask
        mask = valid_len_mask(T, valid_len, B).astype(jnp.int8)
    mask_k = jnp.zeros((T_p, B), jnp.int8).at[:T].set(mask).reshape(K, Tc, B)
    live = _stage_live(blocks, S, Lb)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    def body(own_l, below_l, peep_l, bias_l, sig_lut, tanh_lut, accx_l,
             h0_l, c0_l, live_l, mask_l):
        """SPMD body on device (s, r, c): own_l (1, Lb, r_l, c_l, 4, t, t)
        stationary for the whole utterance; below_l (1, Lb, r_l, c_h, 4,
        t, t) feeds the per-chunk prefix fold; accx_l (K, Tc, B, r_l, 4,
        t) is layer 0's hoisted x prefix for this row block."""
        s_idx = jax.lax.axis_index(stage_axis)
        col = jax.lax.axis_index(col_axis)
        own_l, below_l = own_l[0], below_l[0]
        peep32 = peep_l[0].astype(jnp.int32)
        bias32 = bias_l[0].astype(jnp.int32)
        live_l = live_l[0]

        def hop(acc, p):
            return _sat16(acc + p), None

        def layer_chunk(own_i, peep_i, bias_i, acc_chunk, h0f, c0b,
                        m_chunk):
            def step(carry_i, inp):
                h_full, c_blk = carry_i
                acc_t, m = inp
                h_cols = jax.lax.dynamic_slice(
                    h_full, (0, col * (c_l * t)),
                    (B, c_l * t)).reshape(B, c_l, t)
                parts = _sat16(jnp.einsum('rlgij,blj->lbrgi',
                                          own_i.astype(jnp.int32),
                                          h_cols.astype(jnp.int32)))
                # Engine-order hop replay from the below-region prefix.
                parts_all = jax.lax.all_gather(parts, col_axis, axis=0,
                                               tiled=True)
                pre_acc, _ = jax.lax.scan(hop, acc_t, parts_all)
                h8, c8 = _quantized_state_update(
                    pre_acc, c_blk.astype(jnp.int32), peep_i, bias_i,
                    sig_lut[0], tanh_lut[0])
                h_full_new = jax.lax.all_gather(
                    h8.reshape(B, r_l * t), row_axis, axis=1, tiled=True)
                # Masked step = identity on the carried codes (pure select).
                live_step = (m > 0)[:, None]
                h_full_new = jnp.where(live_step, h_full_new, h_full)
                c8 = jnp.where(live_step[:, :, None], c8, c_blk)
                return (h_full_new, c8), (h_full_new, c8)

            (h_T, c_T), (hs_c, cs_c) = jax.lax.scan(step, (h0f, c0b),
                                                    (acc_chunk, m_chunk))
            return hs_c, cs_c, h_T, c_T

        def macro(carry_m, m_idx):
            h_state, c_state, out_prev = carry_m
            k = m_idx - s_idx
            act = (k >= 0) & (k < K)
            kc = jnp.clip(k, 0, K - 1)
            handed = (out_prev if S == 1 else
                      jax.lax.ppermute(out_prev, stage_axis, fwd_perm))
            accx_chunk = jax.lax.dynamic_index_in_dim(accx_l, kc, 0,
                                                      keepdims=False)
            m_chunk = jnp.where(
                act, jax.lax.dynamic_index_in_dim(mask_l, kc, 0,
                                                  keepdims=False),
                jnp.int8(0))
            below = handed
            hs_slots, cs_slots, new_h, new_c = [], [], [], []
            for i in range(Lb):
                def run_slot(ops, i=i):
                    below_i, h0f, c0b = ops
                    # Chunk-hoisted below/x-region prefix: h-independent
                    # within the step, so it folds once per chunk (the
                    # shared saturation/hop order of _x_prefix_fold —
                    # bit-identical to folding inside the step loop).
                    below_cols = below_i.reshape(Tc, B, c_h, t)
                    acc_chunk = _x_prefix_fold(below_l[i], below_cols)
                    if i == 0:
                        acc_chunk = acc_chunk + jnp.where(s_idx == 0,
                                                          accx_chunk, 0)
                    return layer_chunk(own_l[i], peep32[i], bias32[i],
                                       acc_chunk, h0f, c0b, m_chunk)

                def skip_slot(ops):
                    # Fill/drain bubble or passthrough slot: hand the input
                    # through, carry codes untouched, no compute (the
                    # emitted entries of a skipped macro-step are never
                    # gathered).
                    below_i, h0f, c0b = ops
                    return (below_i,
                            jnp.zeros((Tc, B, r_l, t), jnp.int8),
                            h0f, c0b)

                # Stage-uniform predicate, as in the f32 body: every
                # device of a stage's (row, col) collective groups takes
                # the same branch.
                hs_c, cs_c, h_T, c_T = jax.lax.cond(
                    act & (live_l[i] > 0), run_slot, skip_slot,
                    (below, h_state[i], c_state[i]))
                below = hs_c
                hs_slots.append(hs_c)
                cs_slots.append(cs_c.reshape(Tc, B, r_l * t))
                new_h.append(h_T)
                new_c.append(c_T)
            return ((jnp.stack(new_h), jnp.stack(new_c), below),
                    (jnp.stack(hs_slots), jnp.stack(cs_slots)))

        nl = jnp.sum((live_l > 0).astype(jnp.int32))
        # Static per-stage live counts drive the same stage-uniform branch
        # specialization as the f32 body: single-layer stages replay the
        # sequential chunk scan verbatim, cnt-layer stages walk the
        # Tc + cnt - 1 diagonals with cnt-sliced operands.
        counts = sorted({hi - lo for lo, hi in blocks if hi > lo})

        def macro_batched(carry_m, m_idx):
            # Diagonal-major in-stage order, mirroring the f32 body: slot i
            # runs step t = d - i at diagonal d, its below codes being slot
            # i-1's carried post-step h from diagonal d-1.  Every integer
            # op — the slot-vmapped below/x prefix fold, the engine-order
            # own-h hop scan, the LUT tail — replays in the sequential
            # order, so the emitted codes are bit-identical.
            h_state, c_state, out_prev = carry_m
            k = m_idx - s_idx
            act = (k >= 0) & (k < K)
            kc = jnp.clip(k, 0, K - 1)
            handed = (out_prev if S == 1 else
                      jax.lax.ppermute(out_prev, stage_axis, fwd_perm))
            accx_chunk = jax.lax.dynamic_index_in_dim(accx_l, kc, 0,
                                                      keepdims=False)
            m_chunk = jnp.where(
                act, jax.lax.dynamic_index_in_dim(mask_l, kc, 0,
                                                  keepdims=False),
                jnp.int8(0))

            def fold0(handed_c):
                # Slot 0's below/x prefix folds once per chunk from the
                # handed codes — the identical hoisted ops of the
                # sequential slot loop.
                acc0 = _x_prefix_fold(below_l[0],
                                      handed_c.reshape(Tc, B, c_h, t))
                return acc0 + jnp.where(s_idx == 0, accx_chunk, 0)

            def run_single(ops):
                # cnt == 1 stage: exactly the sequential single-slot chunk
                # scan, no dead-slot compute on the padding slots.
                handed_c, h0_all, c0_all = ops
                hs_c, cs_c, h_T0, c_T0 = layer_chunk(
                    own_l[0], peep32[0], bias32[0], fold0(handed_c),
                    h0_all[0], c0_all[0], m_chunk)
                h_T = jnp.concatenate([h_T0[None], h0_all[1:]], axis=0)
                c_T = jnp.concatenate([c_T0[None], c0_all[1:]], axis=0)
                pad_h = jnp.zeros((Lb - 1, Tc, B, R * t), jnp.int8)
                pad_c = jnp.zeros((Lb - 1, Tc, B, r_l * t), jnp.int8)
                return (h_T, c_T, hs_c,
                        jnp.concatenate([hs_c[None], pad_h], axis=0),
                        jnp.concatenate(
                            [cs_c.reshape(Tc, B, r_l * t)[None], pad_c],
                            axis=0))

            def make_run(cnt):
                def run_cnt(ops):
                    handed_c, h0_all, c0_all = ops
                    acc0 = fold0(handed_c)
                    D = Tc + cnt - 1
                    # Precompute the diagonal geometry, validity masks and
                    # the slot-0 prefix replay once per macro-step; the
                    # diagonal scan consumes them as xs.
                    t_idx = (jnp.arange(D)[:, None]
                             - jnp.arange(cnt)[None, :])
                    valid = (t_idx >= 0) & (t_idx < Tc)
                    t_clip = jnp.clip(t_idx, 0, Tc - 1)
                    acc0_d = acc0[jnp.clip(jnp.arange(D), 0, Tc - 1)]
                    keep_d = ((jnp.take(m_chunk, t_clip, axis=0) > 0)
                              & valid[..., None])
                    own_c = own_l[:cnt]
                    below_c = below_l[1:cnt]
                    peep_c, bias_c = peep32[:cnt], bias32[:cnt]

                    def diag(carry_d, xs_d):
                        h_all, c_all = carry_d
                        acc0_t, keep_t = xs_d
                        # Per-diagonal fold only covers slots 1..cnt-1
                        # (through the ONE shared ``_x_prefix_fold``,
                        # vmapped over slots; per-element hop order
                        # unchanged).
                        acc_rest = jax.vmap(_x_prefix_fold)(
                            below_c, h_all[:-1].reshape(cnt - 1, 1, B, c_h,
                                                        t))[:, 0]
                        acc_t = jnp.concatenate([acc0_t[None], acc_rest],
                                                axis=0)
                        h_cols = jax.lax.dynamic_slice(
                            h_all, (0, 0, col * (c_l * t)),
                            (cnt, B, c_l * t)).reshape(cnt, B, c_l, t)
                        parts = _sat16(jnp.einsum('zrlgij,zblj->lzbrgi',
                                                  own_c.astype(jnp.int32),
                                                  h_cols.astype(jnp.int32)))
                        parts_all = jax.lax.all_gather(parts, col_axis,
                                                       axis=0, tiled=True)
                        pre_acc, _ = jax.lax.scan(hop, acc_t, parts_all)
                        h8, c8 = _quantized_state_update(
                            pre_acc, c_all.astype(jnp.int32),
                            peep_c[:, None], bias_c[:, None], sig_lut[0],
                            tanh_lut[0])
                        h_full_new = jax.lax.all_gather(
                            h8.reshape(cnt, B, r_l * t), row_axis, axis=2,
                            tiled=True)
                        h_next = jnp.where(keep_t[:, :, None], h_full_new,
                                           h_all)
                        c_next = jnp.where(keep_t[:, :, None, None], c8,
                                           c_all)
                        return (h_next, c_next), (h_next, c_next)

                    (h_Tc, c_Tc), (hs_d, cs_d) = jax.lax.scan(
                        diag, (h0_all[:cnt], c0_all[:cnt]),
                        (acc0_d, keep_d))
                    hs_sl = jnp.stack([hs_d[i:i + Tc, i]
                                       for i in range(cnt)])
                    cs_sl = jnp.stack(
                        [cs_d[i:i + Tc, i] for i in range(cnt)]
                    ).reshape(cnt, Tc, B, r_l * t)
                    out = hs_sl[cnt - 1]
                    h_T = jnp.concatenate([h_Tc, h0_all[cnt:]], axis=0)
                    c_T = jnp.concatenate([c_Tc, c0_all[cnt:]], axis=0)
                    hs_sl = jnp.concatenate(
                        [hs_sl, jnp.zeros((Lb - cnt, Tc, B, R * t),
                                          jnp.int8)], axis=0)
                    cs_sl = jnp.concatenate(
                        [cs_sl, jnp.zeros((Lb - cnt, Tc, B, r_l * t),
                                          jnp.int8)], axis=0)
                    return h_T, c_T, out, hs_sl, cs_sl
                return run_cnt

            def skip_macro(ops):
                handed_c, h0_all, c0_all = ops
                return (h0_all, c0_all, handed_c,
                        jnp.zeros((Lb, Tc, B, R * t), jnp.int8),
                        jnp.zeros((Lb, Tc, B, r_l * t), jnp.int8))

            # Branch index is stage-uniform (s_idx/m_idx and per-stage
            # data), as in the sequential macro's predicates.
            branches = [skip_macro] + [
                (run_single if c == 1 else make_run(c)) for c in counts]
            idx = sum(((nl > c).astype(jnp.int32) for c in counts),
                      jnp.int32(0))
            branch = jnp.where(act & (nl > 0), 1 + idx, 0)
            h_T, c_T, out, hs_sl, cs_sl = jax.lax.switch(
                branch, branches, (handed, h_state, c_state))
            return (h_T, c_T, out), (hs_sl, cs_sl)

        macro_fn = (macro_batched
                    if in_stage == 'batched' and Lb > 1 else macro)
        out0 = jnp.zeros((Tc, B, R * t), jnp.int8)
        _, (hs_all, cs_all) = jax.lax.scan(
            macro_fn, (h0_l[0], c0_l[0], out0), jnp.arange(M))
        return hs_all, cs_all

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(stage_axis, None, row_axis, col_axis),
                  P(stage_axis, None, row_axis),
                  P(stage_axis, None, row_axis),
                  P(stage_axis, None, row_axis),
                  P(None), P(None),
                  P(None, None, None, row_axis),
                  P(stage_axis),
                  P(stage_axis, None, None, row_axis),
                  P(stage_axis),
                  P(None)),
        out_specs=(P(None, stage_axis),
                   P(None, stage_axis, None, None, row_axis)),
        check_vma=False,
    )
    hs_g, cs_g = fn(own_s, below_s, peep_s, bias_s,
                    qps[0].sig_lut.reshape(1, 256),
                    qps[0].tanh_lut.reshape(1, 256),
                    acc_x, h0_s, c0_s, live, mask_k)
    hs_g = hs_g.reshape(M, S, Lb, Tc, B, padded_h)
    cs_g = cs_g.reshape(M, S, Lb, Tc, B, padded_h)

    def layer_traj(g, layer):
        s_i, slot = _stage_of(blocks, layer)
        return g[s_i:s_i + K, s_i, slot].reshape(T_p, B, padded_h)[:T]

    out = layer_traj(hs_g, L - 1)[:, :, :p0.n_h]
    if not return_state:
        return out
    h_q = jnp.stack([layer_traj(hs_g, l)[-1] for l in range(L)])
    c_q = jnp.stack([layer_traj(cs_g, l)[-1] for l in range(L)])
    return out, (h_q, c_q)
