"""Batched slot initialisation at admission (``StreamingEngine.
_apply_admissions``).

A step's fresh streams are zeroed by one jitted reset over an ``(S,)`` slot
mask, and each resumed stream's saved rows are written back by one jitted
restore.  The result is bit-equal to the per-slot sequence it replaces
(``.at[slot].set(0)`` per fresh stream, ``.at[slot].set(saved)`` per
resumed one) on recycled slots that hold non-zero, negative-zero and NaN
state; the reset compiles once whatever the number admitted; and the
``serving.admit`` span counts the device calls admission made.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.registry import get_bundle
from repro.serving import StreamingEngine
from repro.serving.session import StreamSession
from repro.serving.spans import serving_events

S = 5


@pytest.fixture(scope='module')
def model():
    cfg = configs.get_smoke_config('chipmunk-ctc').replace(
        lstm_backend='xla_scan')
    params, _ = get_bundle(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _engine(model, **kw):
    cfg, params = model
    return StreamingEngine(cfg, params, max_streams=S, chunk=4, **kw)


def _recycled_states(eng, rng):
    """Packed per-layer ``(h, c)`` rows as previous occupants left them:
    random values, with a negative zero and a NaN planted in every array."""
    out = []
    for h, c in eng.states:
        pair = []
        for a in (h, c):
            v = rng.standard_normal(a.shape).astype(np.float32)
            v[0, 0], v[-1, -1] = -0.0, np.nan
            pair.append(jnp.asarray(v))
        out.append(tuple(pair))
    return tuple(out)


def _session(eng, rng, sid, resumed):
    sess = StreamSession(sid=sid, frames=np.zeros((3, eng.cfg.lstm_inputs),
                                                  np.float32))
    if resumed:
        sess.saved_state = tuple(
            (rng.standard_normal(h.shape[1:]).astype(np.float32),
             rng.standard_normal(c.shape[1:]).astype(np.float32))
            for h, c in eng.states)
    return sess


def _per_slot(states, admissions):
    """The per-slot admission sequence the batched one replaces."""
    for slot, sess in admissions:
        if sess.saved_state is not None:
            states = tuple(
                (h.at[slot].set(jnp.asarray(rh)),
                 c.at[slot].set(jnp.asarray(rc)))
                for (h, c), (rh, rc) in zip(states, sess.saved_state))
        else:
            states = jax.tree.map(lambda a: a.at[slot].set(0), states)
    return states


def _bits(tree):
    return [np.asarray(a).view(np.uint32) for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize('fresh,resumed', [
    ((2,), ()),
    ((0, 2, 4), ()),
    (tuple(range(S)), ()),
    ((0, 3), (1,)),
    ((), (2, 4)),
], ids=['one', 'three', 'all', 'fresh+resumed', 'resumed'])
def test_batched_admission_equals_per_slot_sets_bit_for_bit(model, fresh,
                                                            resumed):
    eng = _engine(model)
    rng = np.random.default_rng(len(fresh) * 7 + len(resumed))
    states = _recycled_states(eng, rng)
    admissions = sorted(
        [(slot, _session(eng, rng, slot, False)) for slot in fresh]
        + [(slot, _session(eng, rng, slot, True)) for slot in resumed],
        key=lambda row: row[0])
    got = eng._apply_admissions(states, admissions)
    want = _per_slot(states, admissions)
    for g, w in zip(_bits(got), _bits(want)):
        np.testing.assert_array_equal(g, w)
    # untouched slots keep their (NaN / negative-zero) bits
    for g, s in zip(_bits(got), _bits(states)):
        keep = [i for i in range(S) if i not in fresh + resumed]
        np.testing.assert_array_equal(g[keep], s[keep])
    assert eng._admission_dispatches(admissions) == (
        int(bool(fresh)) + len(resumed))


def test_reset_compiles_once_for_any_admission_count(model):
    """Refills that admit 1, 3 and all S streams reuse one compiled reset."""
    eng = _engine(model)
    rng = np.random.default_rng(0)
    sizes = []
    for n in (1, 3, S):
        for slot, _ in eng.sched.active():
            eng.sched.finish(slot)
        for _ in range(n):
            eng.submit(rng.standard_normal(
                (3, eng.cfg.lstm_inputs)).astype(np.float32))
        sizes.append(len(eng._refill()))
    assert sizes == [1, 3, S]
    assert eng._reset_slots._cache_size() == 1
    assert eng._restore_slot._cache_size() == 0


def test_admit_span_counts_device_calls_of_admission(model, tmp_path):
    """``dispatches`` reads 1 on a step that admits fresh streams, 0 on a
    step that admits none, and 1 + 1 where a resumed stream joins fresh
    ones (the reset plus one restore)."""
    eng = _engine(model)
    rng = np.random.default_rng(1)

    def utt(n):
        return rng.standard_normal((n, eng.cfg.lstm_inputs)).astype(
            np.float32)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for n in (9, 9, 9):
            eng.submit(utt(n))
        eng.step()                      # admits 3 fresh streams
        eng.step()                      # admits none
        sess = eng.preempt(0)           # requeued with its saved rows
        assert sess is not None and sess.saved_state is not None
        eng.submit(utt(5))
        eng.step()                      # admits the resumed stream + 1 fresh
        assert sess.saved_state is None
        eng.run()
    finally:
        jax.profiler.stop_trace()
    ev = serving_events(str(tmp_path))
    counts = [(c['streams'], c['dispatches']) for _, _, c in ev['admit']]
    assert counts[:3] == [(3, 1), (0, 0), (2, 2)]
    assert all(d == (1 if n else 0) for n, d in counts[3:])
    assert [e['kind'] for e in eng.events].count('resume') == 1
