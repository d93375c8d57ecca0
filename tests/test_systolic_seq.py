"""Multi-engine systolic scale-out of the persistent LSTM sequence kernel.

The float path must be allclose to scanning ``systolic_cell_tiled`` (and to
``core.lstm.lstm_layer``); the int8 path must be *bit-identical* to
``systolic_layer_quantized`` (the silicon datapath) — on real multi-device
meshes.  The STAGED scale-out (DESIGN.md §9, backend
``pallas_seq_fused_systolic``) additionally pins contiguous layer blocks to
a live ``stage`` axis and must match the layerwise composition (f32
allclose + grads) and the single-engine fused stack (int8 bit-identical,
including the chunked code carry).  Multi-device cases run in subprocesses
with a forced host platform device count (see tests/_subproc.py); 2 devices
keeps them safe on the 2-core CI boxes (the cpu_count skip-gate only
applies to the 256-chip LM compile, not to these small meshes).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_with_devices
from repro.core import lstm, quant, systolic
from repro.kernels.lstm_seq import lstm_layer_seq, lstm_layer_seq_quantized


# ----------------------------------------------------------- 2-device meshes
def test_scaleout_float_matches_tiled_and_dense_2dev():
    """systolic_lstm_seq == scanned systolic_cell_tiled == lstm_layer on both
    2-device orientations (row scale-out and col scale-out)."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, systolic
p = lstm.init_lstm_params(jax.random.PRNGKey(0), 23, 37)
xs = jax.random.normal(jax.random.PRNGKey(1), (7, 3, 23)) * 0.5
hs_ref, (hT_ref, cT_ref) = lstm.lstm_layer(p, xs)
hs_tiled = systolic.systolic_layer_tiled(
    systolic.pack_lstm(p, systolic.SystolicPlan(23, 37, 16)), xs)
np.testing.assert_allclose(hs_tiled, hs_ref, rtol=1e-5, atol=1e-6)
for rows, cols in ((2, 1), (1, 2)):
    mesh = systolic.make_systolic_mesh(rows, cols)
    hs, (h_T, c_T) = systolic.systolic_lstm_seq(p, mesh, xs)
    np.testing.assert_allclose(hs, hs_tiled, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(hs, hs_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h_T, hT_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c_T, cT_ref, rtol=1e-5, atol=1e-6)
print('OK')
""", n_devices=2)
    assert 'OK' in out


def test_scaleout_nonzero_state_and_paper_width_2dev():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, systolic
p = lstm.init_lstm_params(jax.random.PRNGKey(0), 123, 421)
xs = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 123)) * 0.5
h0 = jax.random.normal(jax.random.PRNGKey(2), (2, 421)) * 0.3
c0 = jax.random.normal(jax.random.PRNGKey(3), (2, 421)) * 0.3
hs_ref, (hT_ref, cT_ref) = lstm.lstm_layer(p, xs, h0, c0)
mesh = systolic.make_systolic_mesh(1, 2)
hs, (h_T, c_T) = systolic.systolic_lstm_seq(p, mesh, xs, h0, c0)
np.testing.assert_allclose(hs, hs_ref, rtol=1e-5, atol=1e-6)
np.testing.assert_allclose(c_T, cT_ref, rtol=1e-5, atol=1e-6)
print('OK')
""", n_devices=2)
    assert 'OK' in out


def test_scaleout_grad_matches_scan_vjp_2dev():
    """The scale-out custom VJP (gate recompute) == the hand-written scan VJP
    — training must work when auto-selection picks the distributed backend."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, systolic
p = lstm.init_lstm_params(jax.random.PRNGKey(9), 24, 32)
xs = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 24)) * 0.5
mesh = systolic.make_systolic_mesh(2, 1)
def loss(q):
    hs, (hT, cT) = systolic.systolic_lstm_seq(q, mesh, xs)
    return jnp.sum(hs ** 2) + jnp.sum(hT * cT)
def loss_ref(q):
    hs, (hT, cT) = lstm.lstm_layer_fused(q, xs, backend='xla_scan')
    return jnp.sum(hs ** 2) + jnp.sum(hT * cT)
g = jax.grad(loss)(p)
g_ref = jax.grad(loss_ref)(p)
for name, a, b in zip(p._fields, g_ref, g):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
print('OK')
""", n_devices=2)
    assert 'OK' in out


def test_scaleout_quantized_bit_identical_2dev():
    """int8 scale-out == systolic_layer_quantized bit for bit: the gathered
    hop replay must reproduce the chip's saturation order exactly."""
    out = run_with_devices("""
import jax, jax.numpy as jnp
from repro.core import lstm, quant, systolic
p = lstm.init_lstm_params(jax.random.PRNGKey(5), 48, 64)
xs = jax.random.normal(jax.random.PRNGKey(6), (6, 3, 48)) * 0.5
qp = systolic.quantize_packed(
    systolic.pack_lstm(p, systolic.SystolicPlan(48, 64, 16)))
xs_q = quant.quantize(xs, quant.STATE_FMT)
hs_ref = systolic.systolic_layer_quantized(qp, xs_q)
for rows, cols in ((2, 1), (1, 2)):
    mesh = systolic.make_systolic_mesh(rows, cols)
    hs = systolic.systolic_lstm_seq_quantized(qp, mesh, xs_q)
    assert hs.dtype == jnp.int8
    assert bool(jnp.all(hs == hs_ref)), (rows, cols)
# an engine grid that does not divide the mesh is rejected (R=3 over 2 rows)
qp3 = systolic.quantize_packed(
    systolic.pack_lstm(lstm.init_lstm_params(jax.random.PRNGKey(7), 16, 48),
                       systolic.SystolicPlan(16, 48, 16)))
try:
    systolic.systolic_lstm_seq_quantized(
        qp3, systolic.make_systolic_mesh(2, 1), jnp.zeros((3, 2, 16), jnp.int8))
    raise SystemExit('expected ValueError')
except ValueError:
    pass
print('OK')
""", n_devices=2)
    assert 'OK' in out


def test_scaleout_auto_dispatch_2dev():
    """Installing a topology makes ``auto`` pick the scale-out backend and the
    full dispatch path (lstm_layer_fused) stays allclose to the scan."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, systolic
from repro.launch.mesh import install_systolic_topology
mesh = install_systolic_topology('1x2')
assert systolic.current_mesh() is mesh
assert systolic.seq_scaleout_admissible(421, mesh)
# a per-device block that cannot fit the budget is rejected
assert not systolic.seq_scaleout_admissible(1 << 14, mesh, vmem_budget=1 << 20)
assert lstm.select_lstm_backend(23, 37, 16, 3) == 'pallas_seq_systolic'
p = lstm.init_lstm_params(jax.random.PRNGKey(0), 23, 37)
xs = jax.random.normal(jax.random.PRNGKey(1), (16, 3, 23)) * 0.5
hs, _ = lstm.lstm_layer_fused(p, xs, backend='auto')
hs_ref, _ = lstm.lstm_layer_fused(p, xs, backend='xla_scan')
np.testing.assert_allclose(hs, hs_ref, rtol=1e-5, atol=1e-6)
systolic.clear_mesh()
assert lstm.select_lstm_backend(23, 37, 16, 3, platform='cpu') == 'xla_scan'
# a live non-systolic mesh is rejected, not silently misplaced
dm = jax.make_mesh((1, 2), ('data', 'model'))
assert not systolic.seq_scaleout_admissible(37, dm)
try:
    systolic.systolic_lstm_seq(p, dm, xs)
    raise SystemExit('expected ValueError')
except ValueError:
    pass
print('OK')
""", n_devices=2)
    assert 'OK' in out


# ----------------------------------------------- single-device degenerations
def test_scaleout_none_mesh_delegates_to_seq_kernel():
    """mesh=None (and all-1 meshes) degenerate to the PR-1 persistent kernel
    — the composition the scale-out generalises."""
    p = lstm.init_lstm_params(jax.random.PRNGKey(0), 24, 32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 24)) * 0.5
    hs_ref, (hT_ref, cT_ref) = lstm.lstm_layer(p, xs)
    hs, (h_T, c_T) = systolic.systolic_lstm_seq(p, None, xs)
    np.testing.assert_allclose(hs, hs_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h_T, hT_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c_T, cT_ref, rtol=1e-5, atol=1e-6)


def test_scaleout_quantized_none_mesh_delegates():
    """mesh=None degenerates to the whole-sequence int8 kernel (bit-identical
    to the reference scan by the kernel's own contract)."""
    p = lstm.init_lstm_params(jax.random.PRNGKey(0), 16, 48)
    qp = systolic.quantize_packed(
        systolic.pack_lstm(p, systolic.SystolicPlan(16, 48, 16)))
    xs = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 16)) * 0.5
    xs_q = quant.quantize(xs, quant.STATE_FMT)
    hs_ref = systolic.systolic_layer_quantized(qp, xs_q)
    hs = systolic.systolic_lstm_seq_quantized(qp, None, xs_q)
    assert hs.dtype == jnp.int8
    assert bool(jnp.all(hs == hs_ref))


def test_admission_rules():
    assert not systolic.seq_scaleout_admissible(421, None)
    # all-1 meshes are degenerate: the single-engine §3.3 platform/shape
    # rules keep deciding (never auto-pick interpret emulation on CPU)
    assert not systolic.seq_scaleout_admissible(
        421, systolic.make_systolic_mesh(1, 1))
    # axis names must match
    from repro.launch.train import local_mesh
    assert not systolic.seq_scaleout_admissible(421, local_mesh())
    # positive + VMEM-budget cases run on a real 2-device mesh in
    # test_scaleout_auto_dispatch_2dev (admissibility needs a live axis)


# ------------------------------------------------------- batched grid (bb)
def test_seq_kernel_batch_grid_matches_core():
    """bb < B: batch blocks iterate outermost over the resident weights."""
    p = lstm.init_lstm_params(jax.random.PRNGKey(0), 32, 48)
    xs = jax.random.normal(jax.random.PRNGKey(1), (4, 20, 32)) * 0.5
    hs_ref, (hT_ref, cT_ref) = lstm.lstm_layer(p, xs)
    hs, (h_T, c_T) = lstm_layer_seq(p, xs, bn=64, bk=64, bb=8, interpret=True)
    np.testing.assert_allclose(hs, hs_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h_T, hT_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c_T, cT_ref, rtol=1e-5, atol=1e-6)


def test_seq_kernel_batch_grid_quantized_bit_identical():
    p = lstm.init_lstm_params(jax.random.PRNGKey(0), 32, 48)
    xs = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 32)) * 0.5
    qp = systolic.quantize_packed(
        systolic.pack_lstm(p, systolic.SystolicPlan(32, 48, 16)))
    xs_q = quant.quantize(xs, quant.STATE_FMT)
    hs_ref = systolic.systolic_layer_quantized(qp, xs_q)
    hs = lstm_layer_seq_quantized(qp, xs_q, bb=4, interpret=True)  # pads B->8
    assert hs.dtype == jnp.int8
    assert bool(jnp.all(hs == hs_ref))


# ------------------------------------------ staged fused-systolic (DESIGN §9)
def test_staged_stack_matches_layerwise_and_grads_2dev():
    """The staged scale-out on a live ('stage','row','col') mesh (2 stages,
    uneven 2+1 layer blocks) == the layerwise composition, forward, finals
    AND gradients (the cross-layer gate-recompute VJP composed across the
    stage boundary)."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, systolic
p = lstm.init_lstm_stack(jax.random.PRNGKey(0), 16, 24, 3)
xs = jax.random.normal(jax.random.PRNGKey(1), (7, 2, 16)) * 0.5
mesh = systolic.make_systolic_mesh(1, 1, stage=2)
assert systolic.stage_layer_blocks(3, 2) == ((0, 2), (2, 3))
ys_ref, fin_ref = lstm.lstm_stack_apply(p, xs, backend='xla_scan')
ys, fin = systolic.systolic_lstm_stack_seq(p, mesh, xs, chunk=2)
np.testing.assert_allclose(ys, ys_ref, rtol=1e-5, atol=1e-6)
for l in range(3):
    np.testing.assert_allclose(fin[l][0], fin_ref[l][0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(fin[l][1], fin_ref[l][1], rtol=1e-5, atol=1e-6)
def loss(q, staged):
    ys, fin = (systolic.systolic_lstm_stack_seq(q, mesh, xs, chunk=2)
               if staged else lstm.lstm_stack_apply(q, xs, backend='xla_scan'))
    return jnp.sum(ys ** 2) + sum(jnp.sum(h * c) for h, c in fin)
g = jax.grad(lambda q: loss(q, True))(p)
g_ref = jax.grad(lambda q: loss(q, False))(p)
for a, b in zip(jax.tree_util.tree_flatten(g_ref)[0],
                jax.tree_util.tree_flatten(g)[0]):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
# a live stage axis with an intra-stage col axis (2 devices as (2,1,1) only;
# the col orientation runs in the scale-out bench on 4 devices)
print('OK')
""", n_devices=2)
    assert 'OK' in out


def test_staged_quantized_bit_identical_and_chunk_carry_2dev():
    """int8 staged path == the silicon reference chain AND the single-engine
    fused stack, bit for bit — including ≥3 ragged masked chunks with the
    opaque per-layer (h_q, c_q) carry and a mid-sequence handoff of the
    staged state INTO the single-engine fused stack (cross-engine state
    handoff for the streaming engine)."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, quant, systolic
from repro.kernels.lstm_seq import lstm_stack_seq_quantized
n_x, n_h, tile, L = 24, 32, 16, 3
st = lstm.init_lstm_stack(jax.random.PRNGKey(5), n_x, n_h, L)
qps = []
for l, lp in enumerate(st.layers):
    plan = systolic.SystolicPlan(n_x if l == 0 else n_h, n_h, tile)
    qps.append(systolic.quantize_packed(systolic.pack_lstm(lp, plan)))
xs = jax.random.normal(jax.random.PRNGKey(6), (6, 2, n_x)) * 0.5
xs_q = quant.quantize(xs, quant.STATE_FMT)
h = xs_q
for qp in qps:
    h = systolic.systolic_layer_quantized(qp, h)
ref = np.asarray(h)
mesh = systolic.make_systolic_mesh(1, 1, stage=2)
out = systolic.systolic_lstm_stack_seq_quantized(qps, mesh, xs_q, chunk=2)
assert out.dtype == jnp.int8
np.testing.assert_array_equal(np.asarray(out), ref)
# == the single-engine fused stack on the same inputs (bit-identical)
fused = lstm_stack_seq_quantized(qps, xs_q, interpret=True)
np.testing.assert_array_equal(np.asarray(out), np.asarray(fused))
# >=3 ragged masked chunks with the opaque per-layer code carry
lens = np.array([6, 3])
stt = None; outs = []
for lo, hi in ((0, 2), (2, 4), (4, 6)):
    vl = jnp.asarray(np.clip(lens - lo, 0, hi - lo), jnp.int32)
    o, stt = systolic.systolic_lstm_stack_seq_quantized(
        qps, mesh, xs_q[lo:hi], state=stt, valid_len=vl, return_state=True,
        chunk=1)
    outs.append(np.asarray(o))
hs = np.concatenate(outs)
for b, Lv in enumerate(lens):
    np.testing.assert_array_equal(hs[:Lv, b], ref[:Lv, b])
    np.testing.assert_array_equal(np.asarray(stt[0])[-1, b, :n_h],
                                  ref[Lv - 1, b])
# cross-engine handoff: staged state -> single-engine fused stack
o1, st1 = systolic.systolic_lstm_stack_seq_quantized(
    qps, mesh, xs_q[:3], return_state=True, chunk=1)
o2 = lstm_stack_seq_quantized(qps, xs_q[3:], state=st1, interpret=True)
np.testing.assert_array_equal(
    np.concatenate([np.asarray(o1), np.asarray(o2)]), ref)
print('OK')
""", n_devices=2)
    assert 'OK' in out


def test_staged_auto_dispatch_and_f32_chunk_carry_2dev():
    """Installing a stage>1 topology makes stack-level ``auto`` resolve to
    the staged backend (stage-aware admission), the full dispatch path
    stays allclose to the scan, and f32 chunked serving with per-layer
    carried state is bit-equal to the monolithic staged call."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, systolic
mesh = systolic.install_mesh(systolic.make_systolic_mesh(1, 1, stage=2))
assert systolic.seq_scaleout_admissible(24, mesh, n_layers=3)
assert not systolic.seq_scaleout_admissible(24, mesh)       # per-layer form
assert not systolic.seq_scaleout_admissible(24, mesh, n_layers=1)  # S > L
assert not systolic.seq_scaleout_admissible(          # VMEM budget rejection
    1 << 13, mesh, n_layers=3, vmem_budget=1 << 20)
assert lstm.select_stack_backend(16, 24, 3, 16, 2) == 'pallas_seq_fused_systolic'
assert lstm.select_stack_backend(16, 24, 3, 2, 2) != 'pallas_seq_fused_systolic'
assert lstm.select_lstm_backend(16, 24, 16, 2, platform='cpu') == 'xla_scan'
p = lstm.init_lstm_stack(jax.random.PRNGKey(0), 16, 24, 3)
xs = jax.random.normal(jax.random.PRNGKey(3), (16, 2, 16)) * 0.5
ys_a, _ = lstm.lstm_stack_apply(p, xs, backend='auto')
ys_x, _ = lstm.lstm_stack_apply(p, xs, backend='xla_scan')
np.testing.assert_allclose(ys_a, ys_x, rtol=1e-5, atol=1e-6)
lens = np.array([9, 5])
mono, mono_fin = lstm.lstm_stack_chunk(
    p, xs[:9], None, valid_len=jnp.asarray(lens),
    backend='pallas_seq_fused_systolic')
stt = None; outs = []
for lo, hi in ((0, 3), (3, 6), (6, 9)):
    vl = jnp.asarray(np.clip(lens - lo, 0, hi - lo), jnp.int32)
    o, stt = lstm.lstm_stack_chunk(p, xs[lo:hi], stt, valid_len=vl,
                                   backend='pallas_seq_fused_systolic')
    outs.append(np.asarray(o))
np.testing.assert_array_equal(np.concatenate(outs), np.asarray(mono))
for l in range(3):
    np.testing.assert_array_equal(np.asarray(stt[l][0]),
                                  np.asarray(mono_fin[l][0]))
systolic.clear_mesh()
print('OK')
""", n_devices=2)
    assert 'OK' in out


def test_staged_none_mesh_degenerates_to_fused_stack():
    """mesh=None (and all-1 meshes) degenerate to the single-engine §8
    fused stack — the composition the staged scale-out pipelines."""
    p = lstm.init_lstm_stack(jax.random.PRNGKey(0), 16, 24, 2)
    xs = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 16)) * 0.5
    ys_ref, _ = lstm.lstm_stack_apply(p, xs, backend='pallas_seq_fused')
    ys, _ = systolic.systolic_lstm_stack_seq(p, None, xs)
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(ys_ref))


def test_staged_admission_and_blocks():
    assert systolic.stage_layer_blocks(3, 3) == ((0, 1), (1, 2), (2, 3))
    assert systolic.stage_layer_blocks(3, 2) == ((0, 2), (2, 3))
    # stages beyond the stack get empty passthrough blocks
    assert systolic.stage_layer_blocks(2, 3) == ((0, 1), (1, 2), (2, 2))
    # stage-aware admission needs a real mesh with the three axes
    assert not systolic.seq_scaleout_admissible(421, None, n_layers=3)
    from repro.launch.train import local_mesh
    assert not systolic.seq_scaleout_admissible(421, local_mesh(), n_layers=3)
    # a stage-1 mesh belongs to the layerwise §6 rule, never the staged one
    assert not systolic.seq_scaleout_admissible(
        421, systolic.make_systolic_mesh(1, 1), n_layers=3)


# ----------------------------------------------------------- topology presets
def test_topology_presets_geometry():
    from repro.launch.mesh import SYSTOLIC_TOPOLOGIES
    # graves-75: the 75-tile 3x(5x5) real-time phoneme configuration
    assert SYSTOLIC_TOPOLOGIES['graves-75'] == (3, 5, 5)
    stage, rows, cols = SYSTOLIC_TOPOLOGIES['graves-75']
    assert stage * rows * cols == 75
    # the CTC layer plan at tile=96 matches the '5x7' preset
    plan = systolic.SystolicPlan(123, 421, 96)
    assert SYSTOLIC_TOPOLOGIES['5x7'] == (1, plan.rows, plan.cols)
    # every stage-1 preset is admissible for the paper layer once built
    for name, (stage, rows, cols) in SYSTOLIC_TOPOLOGIES.items():
        assert stage >= 1 and rows >= 1 and cols >= 1


# ------------------------------------------- in-stage schedule equivalence
def test_staged_in_stage_modes_bit_equal_f32_2dev():
    """Both in-stage round orders (diagonal-batched wavefront vs the
    layer-sequential hoisted form) are BITWISE-equal schedules of the same
    arithmetic: forward outputs, per-layer finals, and grads through the
    gate-recompute VJP, at several chunk sizes including a ragged one."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, systolic
assert systolic.IN_STAGE_MODES == ('batched', 'sequential')
p = lstm.init_lstm_stack(jax.random.PRNGKey(0), 16, 24, 3)
xs = jax.random.normal(jax.random.PRNGKey(1), (9, 2, 16)) * 0.5
mesh = systolic.make_systolic_mesh(1, 1, stage=2)
for chunk in (1, 2, 4, 9):           # 9/2 and 9/4 exercise ragged tails
    ys_b, fin_b = systolic.systolic_lstm_stack_seq(
        p, mesh, xs, chunk=chunk, in_stage='batched')
    ys_s, fin_s = systolic.systolic_lstm_stack_seq(
        p, mesh, xs, chunk=chunk, in_stage='sequential')
    np.testing.assert_array_equal(np.asarray(ys_b), np.asarray(ys_s))
    for l in range(3):
        np.testing.assert_array_equal(np.asarray(fin_b[l][0]),
                                      np.asarray(fin_s[l][0]))
        np.testing.assert_array_equal(np.asarray(fin_b[l][1]),
                                      np.asarray(fin_s[l][1]))
def loss(q, mode):
    ys, fin = systolic.systolic_lstm_stack_seq(q, mesh, xs, chunk=2,
                                               in_stage=mode)
    return jnp.sum(ys ** 2) + sum(jnp.sum(h * c) for h, c in fin)
g_b = jax.grad(lambda q: loss(q, 'batched'))(p)
g_s = jax.grad(lambda q: loss(q, 'sequential'))(p)
for a, b in zip(jax.tree_util.tree_flatten(g_b)[0],
                jax.tree_util.tree_flatten(g_s)[0]):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print('OK')
""", n_devices=2)
    assert 'OK' in out


def test_staged_in_stage_modes_bit_identical_int8_2dev():
    """int8: both in-stage orders == the silicon reference chain bit for
    bit, AND a >=3-ragged-chunk masked carry stream under EACH mode equals
    the other mode's stream exactly (the serving engine may flip modes
    between deployments without perturbing a single code)."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, quant, systolic
n_x, n_h, tile, L = 24, 32, 16, 3
st = lstm.init_lstm_stack(jax.random.PRNGKey(5), n_x, n_h, L)
qps = []
for l, lp in enumerate(st.layers):
    plan = systolic.SystolicPlan(n_x if l == 0 else n_h, n_h, tile)
    qps.append(systolic.quantize_packed(systolic.pack_lstm(lp, plan)))
xs = jax.random.normal(jax.random.PRNGKey(6), (6, 2, n_x)) * 0.5
xs_q = quant.quantize(xs, quant.STATE_FMT)
h = xs_q
for qp in qps:
    h = systolic.systolic_layer_quantized(qp, h)
ref = np.asarray(h)
mesh = systolic.make_systolic_mesh(1, 1, stage=2)
for mode in systolic.IN_STAGE_MODES:
    o = systolic.systolic_lstm_stack_seq_quantized(qps, mesh, xs_q, chunk=2,
                                                   in_stage=mode)
    np.testing.assert_array_equal(np.asarray(o), ref)
lens = np.array([6, 3])
streams = {}
for mode in systolic.IN_STAGE_MODES:
    stt = None; outs = []
    for lo, hi in ((0, 2), (2, 4), (4, 6)):
        vl = jnp.asarray(np.clip(lens - lo, 0, hi - lo), jnp.int32)
        o, stt = systolic.systolic_lstm_stack_seq_quantized(
            qps, mesh, xs_q[lo:hi], state=stt, valid_len=vl,
            return_state=True, chunk=1, in_stage=mode)
        outs.append(np.asarray(o))
    streams[mode] = (np.concatenate(outs), np.asarray(stt[0]))
np.testing.assert_array_equal(streams['batched'][0],
                              streams['sequential'][0])
np.testing.assert_array_equal(streams['batched'][1],
                              streams['sequential'][1])
for b, Lv in enumerate(lens):
    np.testing.assert_array_equal(streams['batched'][0][:Lv, b], ref[:Lv, b])
print('OK')
""", n_devices=2)
    assert 'OK' in out


def test_staged_in_stage_modes_graves75_scaled_2dev():
    """A scaled-down graves-75 shape (3 stages, live row+col sharding is
    covered by the scale-out bench; here 6 devices as (3,2,1)): 5 layers
    over 3 stages gives uneven (2,2,1) blocks — the wavefront diagonals hit
    both a 2-layer block (real batching) and a 1-layer block (degenerate),
    and both orders stay bit-equal."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, systolic
p = lstm.init_lstm_stack(jax.random.PRNGKey(7), 16, 24, 5)
xs = jax.random.normal(jax.random.PRNGKey(8), (8, 2, 16)) * 0.5
mesh = systolic.make_systolic_mesh(2, 1, stage=3)
assert systolic.stage_layer_blocks(5, 3) == ((0, 2), (2, 4), (4, 5))
ys_ref, _ = lstm.lstm_stack_apply(p, xs, backend='xla_scan')
ys_b, _ = systolic.systolic_lstm_stack_seq(p, mesh, xs, chunk=2,
                                           in_stage='batched')
ys_s, _ = systolic.systolic_lstm_stack_seq(p, mesh, xs, chunk=2,
                                           in_stage='sequential')
np.testing.assert_array_equal(np.asarray(ys_b), np.asarray(ys_s))
np.testing.assert_allclose(ys_b, ys_ref, rtol=1e-5, atol=1e-6)
print('OK')
""", n_devices=6)
    assert 'OK' in out
