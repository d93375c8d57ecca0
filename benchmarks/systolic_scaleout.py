"""Systolic scale-out benchmark (DESIGN.md §6 and §9 acceptance rows).

Compares, on a real multi-device ("row","col") mesh, the per-step distributed
scan (``systolic_lstm_shard_map`` — packed ``[x|h]`` column re-assembled and
the x-region re-MACed every timestep) against the persistent distributed
sequence kernel (``systolic_lstm_seq`` — ``W_x @ x`` hoisted once, per-device
weight blocks tile-stationary for all T steps), on the paper's 123->421 CTC
layer at T=128, plus a scaled-down graves-75 (3-layer) configuration.

A second subprocess benches the STAGED scale-out (§9) on the full CTC stack:
the same 50 engines either as ONE flat 5x10 grid running the three layers
back to back (layerwise ``pallas_seq_systolic`` — the best a stage-1
placement can do with that much silicon, and the paper's Sec. 3.3 argument
against flat scaling: the accumulation chain and h-broadcast spans keep
growing) or as TWO pipelined 5x5 stages (``pallas_seq_fused_systolic`` —
stage 0 holds layers {0,1}, stage 1 layer {2}, chunks handed over by
ppermute).  Same arithmetic either way; the staged path wins on rounds
(2(T+Tc) vs 3T sequential steps) and on per-step collective span (5-wide
within a stage vs 10-wide across the flat grid) — the same levers as the
silicon's 3x(5x5) Table-2 row.

A third row times the staged stack with the in-stage diagonals BATCHED
(``in_stage='batched'`` — each stage retires its whole layer block as one
wavefront of Tc+Lb-1 rounds instead of Lb sequential Tc-loops).  On silicon
(and in the cycle model, ``staged_wavefront_cycles(in_stage_batched=True)``)
that trades round count for concurrency and wins ~1.9x; on this host the 50
"devices" time-slice ONE core, the emulation is FLOP-bound, and the
sequential order's hoisted full-width below-GEMMs are FLOP-optimal — so the
measured ratio lands BELOW 1.  The row reports that honestly; the measured-
schedule autotuner (repro.tune) is the per-host decider, and the committed
tuned_schedules.json carries this host's measured winner.  The model/
measurement bracket is pinned in tests/test_perf_model.py.

A fourth subprocess runs the §13 geometry shmoo (`repro.tune.tune_geometry`)
over the same 50-engine budget: every admissible `stages x (rows x cols)`
factorization and stage split in the balanced reference's bit-equality
class, interleaved-timed against the 2x(5x5) Table-2 default.  The winner
row records the measured best honestly even when it IS the default (1.00x).

The driver process must keep seeing a single device (smoke tests/benches run
in it), so this suite spawns subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the same pattern as
tests/_subproc.py — and re-emits the rows they print.  CPU host devices make
the absolute times an emulation, but the compared pairs share per-step
structure, so the ratios are structurally meaningful.
"""
import subprocess
import sys

from .common import emit

N_DEVICES = 20      # the 123->421 plan at tile=128 is a 4x5 engine grid
N_DEVICES_STAGED = 50   # 2 stages x (5x5) == one flat 5x10 grid

_SNIPPET = r"""
import time
import jax, jax.numpy as jnp
from repro.core import lstm, systolic


def t_med(fn, *args, warmup=1, iters=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


n_x, n_h, T, B = 123, 421, 128, 8
p = lstm.init_lstm_params(jax.random.PRNGKey(42), n_x, n_h)
xs = jax.random.normal(jax.random.PRNGKey(43), (T, B, n_x)) * 0.5
plan = systolic.SystolicPlan(n_x, n_h, tile=128)           # 4x5 engine grid
mesh = systolic.make_systolic_mesh(plan.rows, plan.cols)
packed = systolic.shard_packed_lstm(systolic.pack_lstm(p, plan), mesh)
xs_pad = jnp.zeros((T, B, plan.padded_in), xs.dtype).at[..., :n_x].set(xs)

f_step = jax.jit(lambda x: systolic.systolic_lstm_shard_map(packed, mesh, x))
f_seq = jax.jit(lambda x: systolic.systolic_lstm_seq(p, mesh, x)[0])

hs_step = f_step(xs_pad)
hs_seq = f_seq(xs)
err = float(jnp.max(jnp.abs(hs_seq - hs_step)))
assert err < 1e-4, err

# Alternate the two paths per iteration so host-load drift hits both equally
# (back-to-back t_med calls bias whichever runs during a busy window).
steps, seqs = [], []
for _ in range(5):
    t0 = time.perf_counter(); jax.block_until_ready(f_step(xs_pad))
    steps.append(time.perf_counter() - t0)
    t0 = time.perf_counter(); jax.block_until_ready(f_seq(xs))
    seqs.append(time.perf_counter() - t0)
us_step = sorted(steps)[len(steps) // 2] * 1e6
us_seq = sorted(seqs)[len(seqs) // 2] * 1e6
grid = f'{plan.rows}x{plan.cols}'
print(f'ROW|scaleout/per_step_shard_map|{us_step:.1f}|'
      f'T={T} B={B} 123->421 on {grid} mesh '
      f'([x|h] column re-packed + x-region re-MACed every step)')
print(f'ROW|scaleout/persistent_seq|{us_seq:.1f}|'
      f'T={T} B={B} 123->421 on {grid} mesh '
      f'(hoisted W_x@x, tile-stationary blocks; '
      f'{us_step / us_seq:.2f}x vs per-step, max_err={err:.1e})')

# Scaled-down graves-75: the paper's real-time phoneme topology is a 3-stage
# pipeline of 5x5 grids (75 tiles); here the 3 layers run back to back on a
# 2x2 mesh each at ~1:4 width — the same dataflow at CI-friendly scale.
keys = jax.random.split(jax.random.PRNGKey(7), 3)
n_hg, Tg = 104, 64
layers = [lstm.init_lstm_params(keys[0], n_x, n_hg)] + [
    lstm.init_lstm_params(k, n_hg, n_hg) for k in keys[1:]]
mesh_g = systolic.make_systolic_mesh(2, 2)


def stack(x):
    for lp in layers:
        x, _ = systolic.systolic_lstm_seq(lp, mesh_g, x)
    return x


f_g = jax.jit(stack)
xg = jax.random.normal(jax.random.PRNGKey(8), (Tg, B, n_x)) * 0.5
hs_g = f_g(xg)
ref = xg
for lp in layers:
    ref, _ = lstm.lstm_layer(lp, ref)
err_g = float(jnp.max(jnp.abs(hs_g - ref)))
assert err_g < 1e-4, err_g
us_g = t_med(f_g, xg)
print(f'ROW|scaleout/graves_scaled|{us_g:.1f}|'
      f'3 layers {n_x}->{n_hg} T={Tg} B={B}, 2x2 mesh per layer '
      f'(graves-75 = 3x(5x5) topology at 1:4 width, max_err={err_g:.1e})')
"""


_STAGED_SNIPPET = r"""
import time
import jax, jax.numpy as jnp, numpy as np
from repro.core import lstm, systolic

n_x, n_h, T, B, Tc = 123, 421, 128, 8, 16
stack = lstm.init_lstm_stack(jax.random.PRNGKey(42), n_x, n_h, 3)
xs = jax.random.normal(jax.random.PRNGKey(43), (T, B, n_x)) * 0.5
mesh_flat = systolic.make_systolic_mesh(5, 10)          # one flat 5x10 grid
mesh_staged = systolic.make_systolic_mesh(5, 5, stage=2)  # 2 x (5x5) stages


def layerwise(x):
    h = x
    for lp in stack.layers:
        h, _ = systolic.systolic_lstm_seq(lp, mesh_flat, h)
    return h


f_lw = jax.jit(layerwise)
f_st = jax.jit(lambda x: systolic.systolic_lstm_stack_seq(
    stack, mesh_staged, x, chunk=Tc, in_stage='sequential')[0])
f_bt = jax.jit(lambda x: systolic.systolic_lstm_stack_seq(
    stack, mesh_staged, x, chunk=Tc, in_stage='batched')[0])
r_lw = np.asarray(jax.block_until_ready(f_lw(xs)))
r_st = np.asarray(jax.block_until_ready(f_st(xs)))
r_bt = np.asarray(jax.block_until_ready(f_bt(xs)))
err = float(np.abs(r_lw - r_st).max())
assert err < 1e-4, err
np.testing.assert_array_equal(r_bt, r_st)   # schedule change, not numerics

# Alternate the three paths per iteration so host-load drift hits all equally.
lws, sts, bts = [], [], []
for _ in range(5):
    t0 = time.perf_counter(); jax.block_until_ready(f_lw(xs))
    lws.append(time.perf_counter() - t0)
    t0 = time.perf_counter(); jax.block_until_ready(f_st(xs))
    sts.append(time.perf_counter() - t0)
    t0 = time.perf_counter(); jax.block_until_ready(f_bt(xs))
    bts.append(time.perf_counter() - t0)
us_lw = sorted(lws)[len(lws) // 2] * 1e6
us_st = sorted(sts)[len(sts) // 2] * 1e6
us_bt = sorted(bts)[len(bts) // 2] * 1e6
print(f'ROW|scaleout/stack_layerwise_systolic|{us_lw:.1f}|'
      f'T={T} B={B} 123->421x3 on one flat 5x10 grid (50 engines; 3 '
      f'sequential whole-sequence launches, 10-wide psum chain per step)')
print(f'ROW|scaleout/stack_fused_systolic|{us_st:.1f}|'
      f'T={T} B={B} 123->421x3 on a 2-stage 2x(5x5) mesh (same 50 engines; '
      f'layer blocks stage-stationary, Tc={Tc} chunks ppermute-pipelined, '
      f'5-wide collectives; sequential in-stage slot loop; '
      f'{us_lw / us_st:.2f}x vs layerwise flat grid, max_err={err:.1e})')
print(f'ROW|scaleout/stack_fused_systolic_batched|{us_bt:.1f}|'
      f'T={T} B={B} 123->421x3, same 2-stage 2x(5x5) mesh and Tc={Tc} but '
      f'in-stage diagonals batched (Tc+Lb-1 rounds/macro-step vs Lb*Tc); '
      f'{us_st / us_bt:.2f}x vs sequential in-stage, '
      f'{us_lw / us_bt:.2f}x vs layerwise flat grid; bit-equal outputs; '
      f'single-core FLOP-bound emulation, silicon model predicts the '
      f'batched win -- repro.tune picks per host)')
"""


_GEOMETRY_SNIPPET = r"""
import jax
from repro.core import lstm
from repro.tune import ScheduleCache
from repro.tune.autotune import tune_geometry

n_x, n_h, L, T, B = 123, 421, 3, 128, 8
stack = lstm.init_lstm_stack(jax.random.PRNGKey(42), n_x, n_h, L)
xs = jax.random.normal(jax.random.PRNGKey(43), (T, B, n_x)) * 0.5

# Same 50-engine budget as the staged rows above; the balanced 2x(5x5)
# Table-2 placement anchors the baseline AND the bit-equality class.
# tune_geometry interleaves the trials (ref/cand/ref/cand) and asserts
# bitwise-equal outputs inside the class before any clock is read.
entry, records, base_us = tune_geometry(
    stack, xs, devices=50, ref=(2, 5, 5), cache=ScheduleCache(),
    top_k=3, iters=3, warmup=1)
win_us = entry.measured_us
print(f'ROW|scaleout/geometry_balanced_ref|{base_us:.1f}|'
      f'T={T} B={B} 123->421x3 balanced 2x(5x5) dispatch default '
      f'(blocks=2,1 Tc=16; the interleaved baseline arm of the shmoo)')
print(f'ROW|scaleout/geometry_winner|{win_us:.1f}|'
      f'T={T} B={B} 123->421x3 measured geometry winner '
      f'{entry.stages}x({entry.rows}x{entry.cols}) blocks={entry.blocks} '
      f'Tc={entry.tc} {entry.in_stage} within bit-equality class '
      f'(n_h_p=425, bk=85); {base_us / win_us:.2f}x vs balanced ref '
      f'({len(records)} candidates shmooed, VMEM-pruned; margins sit at '
      f'the few-percent run-to-run drift level, so winners may flip '
      f'between runs -- dispatch trusts the separately measured '
      f'tuned_schedules.json entry, not this row)')
"""


def _run_snippet(snippet: str, n_devices: int):
    from repro.launch.mesh import host_device_env
    proc = subprocess.run([sys.executable, '-c', snippet],
                          env=host_device_env(n_devices),
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f'scaleout subprocess failed\nSTDOUT:\n'
                           f'{proc.stdout}\nSTDERR:\n{proc.stderr}')
    rows = [l for l in proc.stdout.splitlines() if l.startswith('ROW|')]
    for row in rows:
        _, name, us, derived = row.split('|', 3)
        emit(name, float(us), derived)
    return rows


def run():
    rows = _run_snippet(_SNIPPET, N_DEVICES)
    rows += _run_snippet(_STAGED_SNIPPET, N_DEVICES_STAGED)
    rows += _run_snippet(_GEOMETRY_SNIPPET, N_DEVICES_STAGED)
    return rows
