"""Schedule strategies + replay harness for the serving conformance suite.

The property-based tests (tests/test_serving_async.py) draw randomized
serving schedules — utterance lengths, priorities, staggered submissions,
preempt/evict/resume control ops, engine-failure and slot-poison injections
— and replay the SAME schedule against a synchronous engine, an async
double-buffered engine, and the monolithic forward, asserting bit-equal
outputs.

Replay is keyed on the engine's COMMITTED step counter (``_step_idx``), not
the host loop iteration: both dispatch modes pass through every committed
step index in order, so each control op fires exactly once at the same
logical point in both replays.  The async engine may have one more chunk in
flight when an op fires (its control-plane barrier commits it first) — that
moves a chunk boundary, which the §7 masking contract makes output-invariant
— but which streams exist, which frames they carry, and every injected fault
index are identical across modes by construction.

Two schedule families, because their conformance arguments differ:

  * **control-op schedules** (``op_schedules``): preempt/evict/resume and
    priority admission interleave with serving; no poison (a moved chunk
    boundary legally changes which SLOT a given stream occupies at a given
    step, so slot-keyed poison could pick different victims per mode).
  * **fault schedules** (``fault_schedules``): deterministic engine-failure
    and slot-poison injections, no control ops (scheduling is then
    bit-reproducible across modes, so the quarantine victim is too).
"""
from __future__ import annotations

import numpy as np

from hypothesis import strategies as st

N_IN_FALLBACK = 13          # smoke config input width (overridden by caller)


def make_utts(lens, n_in):
    """Deterministic utterances for a drawn length list: stream i's frames
    depend only on (i, lens[i], n_in), so every replay — sync, async,
    monolithic — sees identical inputs."""
    return [np.random.RandomState(1000 + 7 * i + L)
            .randn(L, n_in).astype(np.float32) * 0.5
            for i, L in enumerate(lens)]


def op_schedules(max_ops: int = 4):
    """Strategy for control-op schedules: staggered priority submissions
    plus preempt / evict+resume ops keyed on committed step indices.
    Targets are drawn as raw integers and taken mod the stream count at
    replay, so the strategy needs no dependent draws."""
    raw = st.tuples(
        st.lists(st.integers(1, 26), min_size=2, max_size=5),    # lens
        st.lists(st.integers(0, 1), min_size=5, max_size=5),     # priorities
        st.lists(st.integers(0, 4), min_size=5, max_size=5),     # submit_at
        st.lists(st.tuples(st.integers(0, 8),                    # ops: at
                           st.sampled_from(('preempt', 'evict_resume')),
                           st.integers(0, 7)),                   # raw target
                 min_size=0, max_size=max_ops),
    )
    return raw.map(_normalize_op_schedule)


def _normalize_op_schedule(raw):
    lens, priorities, submit_at, ops = raw
    n = len(lens)
    return {
        'lens': list(lens),
        'priorities': [priorities[i % len(priorities)] for i in range(n)],
        'submit_at': [submit_at[i % len(submit_at)] for i in range(n)],
        'ops': [(at, kind, tgt % n) for at, kind, tgt in ops],
        'fail_at': {},
        'poison_at': {},
    }


def fault_schedules():
    """Strategy for fault-injection schedules: engine failures (degradation
    + retry of the same chunk) and slot poisons (quarantine), with plain
    FIFO submissions and no control ops."""
    raw = st.tuples(
        st.lists(st.integers(1, 26), min_size=2, max_size=5),    # lens
        st.lists(st.integers(1, 6), min_size=0, max_size=2),     # fail steps
        st.lists(st.tuples(st.integers(1, 6), st.integers(0, 2)),
                 min_size=0, max_size=1),                        # poisons
    )
    return raw.map(_normalize_fault_schedule)


def _normalize_fault_schedule(raw):
    lens, fail_steps, poisons = raw
    return {
        'lens': list(lens),
        'priorities': [0] * len(lens),
        'submit_at': [0] * len(lens),
        'ops': [],
        'fail_at': {s: 1 for s in fail_steps},
        'poison_at': dict(poisons),
    }


def recovery_schedules():
    """Strategy for fail -> recover -> fail schedules (§14): permanent
    engine failures followed by scheduled heals, with the promotion
    hysteresis drawn too, so replays exercise degrade / heal /
    promote_canary / promote / promote_rejected / flap paths.  Utterances
    are long enough that the committed step counter reaches every drawn
    recovery step (heals are polled at the top of ``step``, keyed on
    committed steps — a drained engine never heals)."""
    raw = st.tuples(
        st.lists(st.integers(48, 96), min_size=2, max_size=3),   # lens
        st.integers(1, 3),                                       # first fail
        st.integers(1, 3),                                       # heal gap
        st.integers(0, 1),                                       # re-fail?
        st.integers(2, 4),                                       # re-fail gap
        st.integers(1, 3),                                       # hysteresis
    )
    return raw.map(_normalize_recovery_schedule)


def _normalize_recovery_schedule(raw):
    lens, fail1, heal_gap, refail, refail_gap, hysteresis = raw
    fail_at = {fail1: 1}
    recover_at = {fail1 + heal_gap: 1}
    if refail:
        f2 = fail1 + heal_gap + refail_gap
        fail_at[f2] = 1
        recover_at[f2 + heal_gap] = 1
    return {
        'lens': list(lens),
        'priorities': [0] * len(lens),
        'submit_at': [0] * len(lens),
        'ops': [],
        'fail_at': fail_at,
        'poison_at': {},
        'recover_at': recover_at,
        'promote_hysteresis': hysteresis,
    }


def run_schedule(eng, utts, sched, max_steps: int = 400):
    """Replay one schedule to completion; returns ``{sid: (log_probs,
    errored)}``.  Submissions and ops trigger when the engine's committed
    step counter reaches their ``at`` (or immediately once the engine goes
    idle — 'no earlier than' semantics, identical in both modes because
    idleness is a function of committed scheduler state)."""
    n = len(utts)
    submitted = [False] * n
    ops_left = sorted(enumerate(sched['ops']),
                      key=lambda kv: (kv[1][0], kv[0]))
    sessions = {}
    for _ in range(max_steps):
        idx = eng._step_idx
        idle = not eng.sched.busy and eng._pending is None
        for i in range(n):
            if not submitted[i] and (sched['submit_at'][i] <= idx or idle):
                sessions[i] = eng.submit(utts[i], sid=i,
                                         priority=sched['priorities'][i])
                submitted[i] = True
                idle = False
        fired = []
        for key, (at, kind, tgt) in ops_left:
            if at <= idx:
                fired.append((key, (at, kind, tgt)))
                if kind == 'preempt':
                    eng.preempt(tgt)
                else:                        # evict_resume
                    sess = eng.evict(tgt)
                    if sess is not None and sess.error is None:
                        eng.resume(sess)
        for f in fired:
            ops_left.remove(f)
        if not eng.step():
            # fully idle: every remaining op would be a no-op (nothing is
            # active or queued), so only unsubmitted streams matter
            if all(submitted):
                break
    else:
        raise AssertionError('schedule did not drain within max_steps')
    eng.run()
    out = {}
    for i in range(n):
        sess = sessions[i]
        out[i] = (sess.full_log_probs(), sess.error is not None)
    return out


def assert_outputs_equal(a, b, context=''):
    """Bit-equality of two ``run_schedule`` results: same streams, same
    quarantine verdicts, identical log-prob bits."""
    assert set(a) == set(b), (context, sorted(a), sorted(b))
    for sid in a:
        lp_a, err_a = a[sid]
        lp_b, err_b = b[sid]
        assert err_a == err_b, (context, sid, err_a, err_b)
        np.testing.assert_array_equal(
            lp_a, lp_b, err_msg=f'{context} sid={sid}')
