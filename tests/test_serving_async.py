"""Property-based serving conformance suite (DESIGN.md §11).

The async double-buffered engine must be a pure scheduling optimisation:
for EVERY admission/eviction/preemption/resume/poison/engine-failure
schedule, its per-stream outputs are bit-equal to the synchronous engine's
and to the monolithic whole-utterance forward — f32 through the packed
engine, int8 through the quantized kernels' opaque carries.  Schedules are
drawn by hypothesis via tests/_serving_strategies.py and replayed against
both dispatch modes.

Also here: the §11 chunk-size policy unit contract, commit-time deadline
accounting under async dispatch (fake clock), the degradation-ladder
differential sweep, and the int8 stack dispatch gate pins (ROADMAP item:
fused-vs-layerwise at small shapes).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

import _serving_strategies as ss
from _subproc import run_with_devices
from repro import configs
from repro.core import lstm, quant, systolic
from repro.core.lstm import (DEGRADATION_LADDER,
                             select_quantized_stack_backend)
from repro.core.perf_model import FRAME_PERIOD_S, realtime_chunk_budget_s
from repro.kernels.lstm_seq import (lstm_stack_seq_quantized,
                                    lstm_stack_seq_quantized_auto)
from repro.models import chipmunk_net
from repro.models.registry import get_bundle
from repro.runtime import ChunkSizePolicy, ServingFaultConfig
from repro.runtime.fault import FaultConfig, FaultTolerantRunner
from repro.serving import SlotScheduler, StreamingEngine

CHUNK = 4
SLOTS = 3


def _setup(backend='xla_scan'):
    cfg = configs.get_smoke_config('chipmunk-ctc').replace(
        lstm_backend=backend)
    params, _ = get_bundle(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


_CFG, _PARAMS = _setup()


def _engine(async_mode, faults=None, policy=None, cfg=None, params=None,
            chunk=CHUNK):
    return StreamingEngine(cfg or _CFG, params if params is not None
                           else _PARAMS, max_streams=SLOTS, chunk=chunk,
                           async_dispatch=async_mode, faults=faults,
                           chunk_policy=policy)


def _mono(utt, cfg=None, params=None):
    lp = chipmunk_net.forward(cfg or _CFG, params if params is not None
                              else _PARAMS, jnp.asarray(utt)[None])
    return np.asarray(jnp.moveaxis(lp, 0, 1))[0]


# ------------------------------------------------- tentpole: conformance
@pytest.mark.timeout(600)
@settings(max_examples=8, deadline=None)
@given(ss.op_schedules())
def test_async_matches_sync_on_control_op_schedules(sched):
    """Randomized priority submissions + preempt/evict/resume interleaved
    with stepping: async outputs == sync outputs, bit for bit, and both
    == the monolithic forward of each utterance."""
    utts = ss.make_utts(sched['lens'], _CFG.lstm_inputs)
    sync_out = ss.run_schedule(_engine(False), utts, sched)
    async_out = ss.run_schedule(_engine(True), utts, sched)
    ss.assert_outputs_equal(sync_out, async_out, context=str(sched))
    for i, utt in enumerate(utts):
        lp, errored = sync_out[i]
        assert not errored, (i, sched)
        np.testing.assert_array_equal(lp, _mono(utt),
                                      err_msg=f'monolithic sid={i}')


@pytest.mark.timeout(600)
@settings(max_examples=8, deadline=None)
@given(ss.fault_schedules())
def test_async_matches_sync_on_fault_schedules(sched):
    """Randomized engine-failure + slot-poison injections: both modes
    degrade/retry/quarantine identically — same surviving outputs (bit for
    bit), same quarantined streams, and the async engine squashes rather
    than leaks any speculative chunk launched across a fault."""
    utts = ss.make_utts(sched['lens'], _CFG.lstm_inputs)

    def faults():
        return ServingFaultConfig(fail_at=dict(sched['fail_at']),
                                  poison_at=dict(sched['poison_at']),
                                  backoff_s=0.0)

    sync_eng = _engine(False, faults=faults())
    async_eng = _engine(True, faults=faults())
    sync_out = ss.run_schedule(sync_eng, utts, sched)
    async_out = ss.run_schedule(async_eng, utts, sched)
    ss.assert_outputs_equal(sync_out, async_out, context=str(sched))
    s_counts = sync_eng.stats()['event_counts']
    a_counts = async_eng.stats()['event_counts']
    for kind in ('quarantine', 'poison_injected', 'fault', 'degrade',
                 'degrade_exhausted'):
        assert s_counts.get(kind, 0) == a_counts.get(kind, 0), \
            (kind, s_counts, a_counts)
    for i, utt in enumerate(utts):
        lp, errored = sync_out[i]
        if not errored and len(lp):
            np.testing.assert_array_equal(
                lp, _mono(utt)[:len(lp)], err_msg=f'monolithic sid={i}')


@pytest.mark.timeout(900)
@settings(max_examples=4, deadline=None)
@given(ss.recovery_schedules())
def test_async_matches_sync_on_recovery_schedules(sched):
    """Randomized fail -> recover -> fail schedules (§14): both dispatch
    modes replay the identical degrade / heal / canary / promote /
    reject trail (same per-kind event counts), every stream completes
    (zero stream loss), outputs are bit-equal across modes and allclose
    to the monolithic forward regardless of which rungs served which
    chunks."""
    cfg, params = _setup('pallas_seq_fused')
    utts = ss.make_utts(sched['lens'], cfg.lstm_inputs)

    def faults():
        return ServingFaultConfig(
            fail_at=dict(sched['fail_at']),
            recover_at=dict(sched['recover_at']),
            promote_hysteresis=sched['promote_hysteresis'],
            backoff_s=0.0)

    sync_eng = _engine(False, faults=faults(), cfg=cfg, params=params)
    async_eng = _engine(True, faults=faults(), cfg=cfg, params=params)
    sync_out = ss.run_schedule(sync_eng, utts, sched)
    async_out = ss.run_schedule(async_eng, utts, sched)
    ss.assert_outputs_equal(sync_out, async_out, context=str(sched))
    s_counts = sync_eng.stats()['event_counts']
    a_counts = async_eng.stats()['event_counts']
    for kind in ('fault', 'degrade', 'degrade_exhausted', 'heal',
                 'promote_canary', 'promote', 'promote_rejected'):
        assert s_counts.get(kind, 0) == a_counts.get(kind, 0), \
            (kind, s_counts, a_counts, sched)
    assert sync_eng.stats()['rung'] == async_eng.stats()['rung']
    for i, utt in enumerate(utts):
        lp, errored = sync_out[i]
        assert not errored, (i, sched)
        np.testing.assert_allclose(lp, _mono(utt, cfg=cfg, params=params),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f'monolithic sid={i}')


@pytest.mark.timeout(600)
@settings(max_examples=5, deadline=None)
@given(ss.op_schedules(max_ops=2))
def test_async_matches_sync_with_chunk_policy(sched):
    """The chunk-size policy moves chunk boundaries (here: deterministic
    step-downs under an infinite budget, identical in both modes); the §7
    masking contract keeps every stream's outputs bit-invariant to it."""
    utts = ss.make_utts(sched['lens'], _CFG.lstm_inputs)
    mk = lambda: ChunkSizePolicy(chunk_max=CHUNK, slack=1e9, patience=2)
    sync_out = ss.run_schedule(_engine(False, policy=mk()), utts, sched)
    async_out = ss.run_schedule(_engine(True, policy=mk()), utts, sched)
    ss.assert_outputs_equal(sync_out, async_out, context=str(sched))
    for i, utt in enumerate(utts):
        np.testing.assert_array_equal(sync_out[i][0], _mono(utt))


def test_async_preempt_resume_checkpoint_roundtrip(tmp_path):
    """Control-plane barrier: preempting mid-flight under async dispatch
    commits the in-flight chunk first, so the checkpointed rows + cursor
    resume bit-equal — including across a fresh engine via the on-disk
    checkpoint."""
    faults = ServingFaultConfig(checkpoint_dir=str(tmp_path), backoff_s=0.0)
    utt = ss.make_utts([22], _CFG.lstm_inputs)[0]
    eng = _engine(True, faults=faults)
    eng.submit(utt, sid=0)
    eng.step()
    eng.step()                       # chunk 0 committed, chunk 1 in flight
    assert eng._pending is not None
    eng.preempt(0, requeue=False)    # barrier: commits chunk 1, snapshots
    assert eng._pending is None

    fresh = _engine(True, faults=ServingFaultConfig(
        checkpoint_dir=str(tmp_path), backoff_s=0.0))
    sess = fresh.resume_from_checkpoint(utt, sid=0)
    cursor = sess.cursor
    assert cursor == 8, 'preempt must have committed BOTH in-flight chunks'
    fresh.run()
    # the resumed stream emits the uninterrupted run's suffix, bit-equal
    np.testing.assert_array_equal(sess.full_log_probs(),
                                  _mono(utt)[cursor:])


def test_async_speculation_squashed_or_serialized_across_faults():
    """The two unclean-commit defenses: a SCHEDULED engine failure
    serializes (no speculative chunk is launched across it, so nothing to
    squash — the fault is handled by retry), while a quarantine the
    speculation could not see SQUASHES the already-launched successor
    (recorded as a ``squash`` event).  Outputs are unaffected either way."""
    # scheduled failure -> serialized: fault handled, zero squashes
    sched = {'lens': [20, 14], 'priorities': [0, 0], 'submit_at': [0, 0],
             'ops': [], 'fail_at': {1: 1}, 'poison_at': {}}
    utts = ss.make_utts(sched['lens'], _CFG.lstm_inputs)
    eng = _engine(True, faults=ServingFaultConfig(fail_at={1: 1},
                                                  backoff_s=0.0))
    out = ss.run_schedule(eng, utts, sched)
    counts = eng.stats()['event_counts']
    assert counts.get('fault', 0) == 1 and counts.get('squash', 0) == 0, \
        counts
    for i, utt in enumerate(utts):
        np.testing.assert_array_equal(out[i][0], _mono(utt))

    # poison -> quarantine at commit -> the speculative successor squashes
    sched = {'lens': [20, 14, 17], 'priorities': [0, 0, 0],
             'submit_at': [0, 0, 0], 'ops': [], 'fail_at': {},
             'poison_at': {1: 0}}
    utts = ss.make_utts(sched['lens'], _CFG.lstm_inputs)
    eng = _engine(True, faults=ServingFaultConfig(poison_at={1: 0},
                                                  backoff_s=0.0))
    out = ss.run_schedule(eng, utts, sched)
    counts = eng.stats()['event_counts']
    assert counts.get('quarantine', 0) == 1, counts
    assert counts.get('squash', 0) >= 1, counts
    assert out[0][1], 'poisoned stream must be quarantined'
    for i in (1, 2):
        assert not out[i][1]
        np.testing.assert_array_equal(out[i][0], _mono(utts[i]))


# --------------------------------------------------- int8 opaque carries
def _quantized_stack(n_x=16, n_h=16, L=2, tile=16, key=5):
    stack = lstm.init_lstm_stack(jax.random.PRNGKey(key), n_x, n_h, L,
                                 n_out=None)
    return [systolic.quantize_packed(systolic.pack_lstm(
        lp, systolic.SystolicPlan(n_x if l == 0 else n_h, n_h, tile)))
        for l, lp in enumerate(stack.layers)]


_QPS = _quantized_stack()


@pytest.mark.timeout(600)
@settings(max_examples=6, deadline=None)
@given(ss.fault_schedules())
def test_int8_opaque_carry_chunk_schedules_bit_identical(sched):
    """Int8 conformance: the schedule's utterance lengths drive randomized
    chunk boundaries with save/restore of the opaque ``(h_q, c_q)`` carries
    (a host numpy round-trip per boundary — the preempt/resume path) through
    the quantized stack kernels; the emitted codes are bit-identical to the
    monolithic call, on the fused wavefront AND the layerwise chain."""
    lens = sched['lens'][:3]
    B = len(lens)
    T = max(lens)
    xs = jax.random.normal(jax.random.PRNGKey(sum(lens)), (T, B, 16)) * 0.5
    xs_q = quant.quantize(xs, quant.STATE_FMT)
    vl = jnp.asarray(lens, jnp.int32)
    ref = np.asarray(lstm_stack_seq_quantized(_QPS, xs_q, valid_len=vl,
                                              interpret=True))
    # chunk plan from the schedule's fault steps (any cut points work)
    cuts = sorted({min(s, T - 1) for s in sched['fail_at']} - {0})
    bounds = [0] + cuts + [T]
    for backend in ('fused', 'layerwise'):
        st_c = None
        outs = []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            v = jnp.asarray(np.clip(np.asarray(lens) - lo, 0, hi - lo),
                            jnp.int32)
            o, st_c = lstm_stack_seq_quantized_auto(
                _QPS, xs_q[lo:hi], state=st_c, valid_len=v,
                return_state=True, interpret=True, backend=backend)
            # preempt/resume: opaque carry round-trips through host numpy
            st_c = tuple(jnp.asarray(np.asarray(p)) for p in st_c)
            outs.append(np.asarray(o))
        hs = np.concatenate(outs)
        for b, L_v in enumerate(lens):
            np.testing.assert_array_equal(hs[:L_v, b], ref[:L_v, b],
                                          err_msg=f'{backend} b={b}')


# ------------------------------------- satellite: int8 stack dispatch gate
def test_quantized_stack_dispatch_pins():
    """The int8 stack gate pins the BENCH_kernels.json evidence: the
    measured losing shape (96 hidden) dispatches layerwise, the paper's
    421-hidden Table-2 stack dispatches fused; degenerate stacks (single
    layer, short T) always run layerwise."""
    assert select_quantized_stack_backend(96, 3, 32, 4) == 'layerwise'
    assert select_quantized_stack_backend(421, 3, 100, 8) == 'fused'
    assert select_quantized_stack_backend(512, 1, 100, 8) == 'layerwise'
    assert select_quantized_stack_backend(512, 3, 4, 8) == 'layerwise'
    # auto dispatch resolves through the gate and stays bit-identical
    xs = jax.random.normal(jax.random.PRNGKey(2), (9, 2, 16)) * 0.5
    xs_q = quant.quantize(xs, quant.STATE_FMT)
    ref = np.asarray(lstm_stack_seq_quantized(_QPS, xs_q, interpret=True))
    auto = np.asarray(lstm_stack_seq_quantized_auto(_QPS, xs_q,
                                                    interpret=True))
    np.testing.assert_array_equal(auto, ref)


# ------------------------------------------- satellite: degradation ladder
@pytest.mark.parametrize('backend', [b for b in DEGRADATION_LADDER
                                     if not b.endswith('_systolic')])
def test_ladder_backends_agree_on_same_streams(backend):
    """Differential backend sweep: every (non-mesh) DEGRADATION_LADDER rung
    serves the same random streams; outputs agree with the xla_scan
    reference to float tolerance, and each rung is self-consistent between
    async and sync dispatch (bit-equal)."""
    sched = {'lens': [13, 7, 19, 4], 'priorities': [0, 1, 0, 0],
             'submit_at': [0, 0, 1, 2], 'ops': [(2, 'preempt', 0)],
             'fail_at': {}, 'poison_at': {}}
    utts = ss.make_utts(sched['lens'], _CFG.lstm_inputs)
    cfg, params = _setup(backend)
    sync_out = ss.run_schedule(
        _engine(False, cfg=cfg, params=params), utts, sched)
    async_out = ss.run_schedule(
        _engine(True, cfg=cfg, params=params), utts, sched)
    ss.assert_outputs_equal(sync_out, async_out, context=backend)
    for i, utt in enumerate(utts):
        np.testing.assert_allclose(sync_out[i][0], _mono(utt),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f'{backend} sid={i}')


def test_ladder_systolic_rung_agrees():
    """The mesh rung of the ladder (pallas_seq_systolic) over 2 host
    devices serves the same streams as xla_scan, async == sync bit-equal,
    allclose to the single-engine reference."""
    import os
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    out = run_with_devices(
        f"import sys; sys.path.insert(0, {tests_dir!r})\n" + """
import numpy as np, jax, jax.numpy as jnp
import _serving_strategies as ss
from repro import configs
from repro.core import systolic
from repro.models import chipmunk_net
from repro.models.registry import get_bundle
from repro.serving import StreamingEngine

systolic.install_mesh(systolic.make_systolic_mesh(1, 2))
cfg = configs.get_smoke_config('chipmunk-ctc').replace(
    lstm_backend='pallas_seq_systolic')
params, _ = get_bundle(cfg).init(jax.random.PRNGKey(0))
sched = {'lens': [13, 7, 19], 'priorities': [0, 0, 0],
         'submit_at': [0, 0, 0], 'ops': [], 'fail_at': {}, 'poison_at': {}}
utts = ss.make_utts(sched['lens'], cfg.lstm_inputs)
outs = {}
for mode in (False, True):
    eng = StreamingEngine(cfg, params, max_streams=3, chunk=4,
                          async_dispatch=mode)
    outs[mode] = ss.run_schedule(eng, utts, sched)
ss.assert_outputs_equal(outs[False], outs[True], context='systolic')
for i, utt in enumerate(utts):
    lp = chipmunk_net.forward(cfg.replace(lstm_backend='xla_scan'), params,
                              jnp.asarray(utt)[None])
    mono = np.asarray(jnp.moveaxis(lp, 0, 1))[0]
    np.testing.assert_allclose(outs[False][i][0], mono,
                               rtol=1e-5, atol=1e-6)
print('OK')
""", n_devices=2)
    assert 'OK' in out


# --------------------------------------------- chunk-size policy contract
def test_chunk_policy_grows_on_miss_and_pins_floor():
    """A deadline miss doubles the chunk (amortising fixed per-chunk
    overhead) and pins a floor: the policy never returns to a size that
    already missed."""
    pol = ChunkSizePolicy(chunk_max=32, chunk_min=1, slack=1.0)
    assert pol.size == 32                      # starts fully amortised
    assert pol.budget_s(8) == realtime_chunk_budget_s(8)
    pol.size = 4                               # force a small current size
    pol.observe(4, dt=10.0)                    # way over 4*10ms
    assert pol.misses == 1 and pol.size == 8
    for _ in range(50):
        pol.observe(8, dt=0.0)                 # perfect from here on
    assert pol.size == 8, 'floor must pin the doubled size'


def test_chunk_policy_steps_down_only_when_provably_safe():
    """Step-down requires ``patience`` consecutive chunks already meeting
    the HALVED budget; observations that only meet the current budget keep
    the size."""
    pol = ChunkSizePolicy(chunk_max=16, chunk_min=2, slack=1.0, patience=3)
    half_budget = pol.budget_s(8)
    for _ in range(10):                        # meets 16's budget, not 8's
        pol.observe(16, dt=half_budget * 1.5)
    assert pol.size == 16
    for _ in range(2):
        pol.observe(16, dt=half_budget * 0.5)
    assert pol.size == 16, 'patience not yet reached'
    pol.observe(16, dt=half_budget * 0.5)
    assert pol.size == 8
    for _ in range(3 * 10):
        pol.observe(pol.size, dt=0.0)
    assert pol.size == 2, 'bounded below by chunk_min'
    assert pol.misses == 0


def test_chunk_policy_budget_is_table2_arrival_rate():
    """The policy budget is the paper's 10 ms MFCC frame-arrival contract:
    ``chunk * FRAME_PERIOD_S * slack`` exactly."""
    pol = ChunkSizePolicy(chunk_max=8, slack=2.5)
    assert pol.budget_s(5) == pytest.approx(5 * FRAME_PERIOD_S * 2.5)
    assert realtime_chunk_budget_s(5, 2.5) == pytest.approx(
        pol.budget_s(5))


# ------------------------- satellite: commit-time deadline under async
def test_deadline_charged_against_commit_not_launch(monkeypatch):
    """Fake clock: a chunk launched at t=0 whose commit resolves at t=5 is
    charged 5s of wall time even though the commit CALL itself was
    instantaneous — ``deadline_miss`` fires against launch-to-commit time
    (the arrival-rate contract), not time spent inside the resolve call."""
    from repro.runtime import fault as fault_mod
    clock = {'t': 100.0}
    monkeypatch.setattr(fault_mod.time, 'time', lambda: clock['t'])
    monkeypatch.setattr(fault_mod.time, 'sleep', lambda s: None)
    runner = FaultTolerantRunner(cfg=FaultConfig(deadline_s=None))

    t_launch = clock['t']
    clock['t'] += 5.0                      # device computed for 5s
    runner.run(0, lambda: 'x', launched_at=t_launch, deadline_s=1.0)
    assert runner.deadline_misses == 1
    miss = [e for e in runner.events if e['kind'] == 'deadline_miss'][0]
    assert miss['dt'] == pytest.approx(5.0)

    # without launched_at the same resolve is charged ~0s: no miss
    runner.run(1, lambda: 'x', deadline_s=1.0)
    assert runner.deadline_misses == 1


def test_engine_async_deadline_accounts_inflight_time(monkeypatch):
    """End to end on the engine: with async dispatch the chunk's wall time
    spans launch -> commit (one host step apart); the recorded per-chunk
    walls are launch-to-commit, not commit-call-only."""
    import repro.serving.engine as engine_mod
    real_time = engine_mod.time.time
    eng = _engine(True, faults=ServingFaultConfig(deadline_s=1e9,
                                                  backoff_s=0.0))
    utt = ss.make_utts([12], _CFG.lstm_inputs)[0]
    eng.submit(utt, sid=0)
    eng.step()                               # launch only
    t_between = real_time()
    eng.step()                               # commits chunk 0
    assert eng.chunk_walls, 'commit must record a wall time'
    rec_launch_to_commit = eng.chunk_walls[0]
    # the recorded span covers the inter-step host time, so it must be at
    # least the time that passed between the two step() calls' bracket
    assert rec_launch_to_commit >= 0
    eng.run()
    np.testing.assert_array_equal(
        eng.sched.done[0].full_log_probs(), _mono(utt))


# --------------------------------------------- scheduler priority contract
def test_scheduler_priority_admission_and_preempt_candidate():
    """Priority ordering: higher classes admit first (FIFO within a class),
    preempted items re-enter at the front of their class, and
    ``preempt_candidate`` fires only when a waiter strictly outranks the
    lowest-priority occupant of a full grid."""

    class Item:
        def __init__(self, name, priority=0):
            self.name, self.priority = name, priority

    sched = SlotScheduler(2)
    a, b = Item('a'), Item('b')
    slo = Item('slo', priority=2)
    bulk = Item('bulk')
    for it in (a, b, bulk, slo):
        sched.submit(it)
    # slo jumps the whole class-0 FIFO (a, b, bulk); class 0 keeps FIFO order
    assert [q.name for q in sched.pending] == ['slo', 'a', 'b', 'bulk']
    admitted = sched.refill()
    assert [it.name for _, it in admitted] == ['slo', 'a']
    assert sched.preempt_candidate() is None     # 'b' does not outrank 'a'
    urgent = Item('urgent', priority=3)
    sched.submit(urgent)
    cand = sched.preempt_candidate()
    assert cand is not None and sched.slots[cand].name == 'a'
    evicted = sched.evict(cand, requeue=True)
    assert evicted.name == 'a'
    # re-enters the FRONT of class 0: before 'b' and 'bulk'
    assert [q.name for q in sched.pending] == ['urgent', 'a', 'b', 'bulk']
    admitted = sched.refill()
    assert [it.name for _, it in admitted] == ['urgent']


def test_engine_priority_preempts_bulk_for_slo_stream():
    """A priority-1 stream submitted while every slot serves bulk streams
    displaces one bulk stream (preempt + checkpoint + requeue) and is
    admitted next step; every stream still completes with monolithic
    outputs (the displaced one resumes bit-equal)."""
    sched = {'lens': [24, 24, 24, 6], 'priorities': [0, 0, 0, 1],
             'submit_at': [0, 0, 0, 2], 'ops': [],
             'fail_at': {}, 'poison_at': {}}
    utts = ss.make_utts(sched['lens'], _CFG.lstm_inputs)
    for mode in (False, True):
        eng = _engine(mode)
        out = ss.run_schedule(eng, utts, sched)
        counts = eng.stats()['event_counts']
        assert counts.get('preempt', 0) >= 1, (mode, counts)
        for i, utt in enumerate(utts):
            np.testing.assert_array_equal(out[i][0], _mono(utt),
                                          err_msg=f'mode={mode} sid={i}')
        # the SLO stream must not wait for a full bulk drain
        slo_done = [e for e in eng.events if e['kind'] == 'preempt']
        assert slo_done, 'bulk stream should have been preempted'
