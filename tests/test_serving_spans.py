"""The serving engine's own trace spans (``serving/spans.py``).

A tiny engine runs under the profiler with more streams than slots, and
two streams submitted mid-run, so admissions happen between steps; in both
dispatch modes.  The trace, read back with JAX's own reader, holds one
``serving.step`` per ``step()``, every phase span inside a step, one
``serving.submit`` per ``submit``, and admission counters that add up to
the streams submitted.  Structure only, no timing.
"""
import jax
import numpy as np
import pytest

from repro import configs
from repro.models.registry import get_bundle
from repro.serving import StreamingEngine
from repro.serving.spans import serving_events

PHASES = ('admit', 'pack', 'dispatch', 'wait', 'fetch', 'deliver')
LENGTHS = (5, 9, 3, 7, 6)
LATE = 2            # streams submitted after the third step


@pytest.fixture(scope='module')
def model():
    cfg = configs.get_smoke_config('chipmunk-ctc').replace(
        lstm_backend='xla_scan')
    params, _ = get_bundle(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


@pytest.mark.parametrize('async_dispatch', [False, True],
                         ids=['sync', 'async'])
def test_engine_spans_split_each_step_by_phase(model, tmp_path,
                                               async_dispatch):
    cfg, params = model
    eng = StreamingEngine(cfg, params, max_streams=2, chunk=4,
                          decode_ctc=True, async_dispatch=async_dispatch)
    rng = np.random.default_rng(0)
    utts = [rng.standard_normal((n, cfg.lstm_inputs)).astype(np.float32)
            for n in LENGTHS]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for u in utts[:-LATE]:
            eng.submit(u)
        steps = 0
        while True:
            steps += 1
            if steps == 3:
                for u in utts[-LATE:]:
                    eng.submit(u)
            if not eng.step():
                break
    finally:
        jax.profiler.stop_trace()
    assert len(eng.sched.done) == len(LENGTHS)

    ev = serving_events(str(tmp_path))
    assert len(ev['step']) == steps
    assert len(ev['submit']) == len(LENGTHS)
    assert set(PHASES) <= set(ev)
    assert set(ev) <= set(PHASES) | {'step', 'submit'}
    assert sum(c.get('streams', 0) for _, _, c in ev['admit']) == len(
        LENGTHS)
    step_iv = np.array([(s, e) for s, e, _ in ev['step']])
    for phase in PHASES:
        for s, e, _ in ev[phase]:
            assert ((step_iv[:, 0] <= s) & (e <= step_iv[:, 1])).any(), (
                phase, s, e)
