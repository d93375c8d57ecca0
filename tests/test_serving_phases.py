"""The phase split of a traced benchmark run (``scripts/serving_phases.py``)
on a hand-made trace whose every number is known."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                       / 'scripts'))
import serving_phases  # noqa: E402
from chipbench.trace import Device, Trace, union  # noqa: E402


def spans(*rows, **counters):
    return [(s, e, dict(counters)) for s, e in rows]


EVENTS = {
    'step': spans((0.1, 0.2), (0.5, 0.6), (1.2, 1.3)),   # last: outside
    'admit': (spans((0.1, 0.13), streams=2, dispatches=1)
              + spans((0.5, 0.51), streams=0, dispatches=0)),
    'pack': spans((0.13, 0.14), (0.51, 0.52)),
    'dispatch': spans((0.14, 0.15), (0.52, 0.53)),
    'wait': spans((0.15, 0.17), (0.53, 0.56)),
    'fetch': spans((0.17, 0.18), (0.56, 0.57)),
    'deliver': spans((0.18, 0.195), (0.57, 0.59)),
    'submit': spans((0.3, 0.31)),
}
# the second run starts 0.1 ms before its dispatch span
RUNS = np.array([[0.145, 0.16], [0.5199, 0.535]])


def trace():
    dev = Device(['op'] * 2, RUNS, ['jit_fwd(7)'] * 2, RUNS, union(RUNS))
    return Trace({'/device:TPU:0': dev}, {
        'chipbench.window': np.array([[0.0, 1.0]]),
        'chipbench.step': np.array([[0.1, 0.2], [0.5, 0.6]]),
        'chipbench.tick_wait': np.array([[0.2, 0.5]])})


def test_reduce_splits_the_step_by_phase():
    res = serving_phases.reduce(EVENTS, trace())
    assert res['steps'] == 2 and res['admitted'] == 2
    assert res['admitting_steps'] == 1
    m = res['metrics']
    assert m['admit_ms_per_stream'] == pytest.approx(20.0)
    assert m['admit_dispatches_per_step'] == pytest.approx(0.5)
    assert m['launch_ms_per_step'] == pytest.approx(20.0)
    assert m['deliver_ms_per_step'] == pytest.approx(27.5)
    assert m['submit_ms_per_step'] == pytest.approx(5.0)
    assert res['coverage'] == pytest.approx(0.925)
    assert res['step_over_harness_step'] == pytest.approx(1.0)
    # the first run covers half of its dispatch span, the second all of it
    assert res['idle_ms_per_step']['dispatch'] == pytest.approx(2.5)
    assert [g[0] for g in res['idle_gaps']] == [
        'step/wait', 'tick_wait/deliver', 'step/admit']


def test_admit_spans_without_dispatch_counter_read_none():
    """Spans from an engine whose admission counts no device calls."""
    events = dict(EVENTS, admit=spans((0.1, 0.13), streams=2)
                  + spans((0.5, 0.51), streams=0))
    m = serving_phases.reduce(events, trace())['metrics']
    assert m['admit_dispatches_per_step'] is None
    assert m['admit_ms_per_stream'] == pytest.approx(20.0)


def test_clock_matches_each_run_to_its_own_dispatch():
    c = serving_phases.reduce(EVENTS, trace())['clock']
    assert (c['runs'], c['inside'], c['start_before_dispatch'],
            c['end_after_wait']) == (2, 1, 1, 0)
    assert c['dispatches_with_runs'] == {'1': 2}
    assert c['max_abs_start_offset_ms'] == pytest.approx(5.0)
    assert c['step_period_ms'] == pytest.approx(380.0)


def test_reduce_without_program_spans_reads_no_split():
    res = serving_phases.reduce({}, trace())
    assert res['steps'] == 0 and 'metrics' not in res
