"""Whole runs of small cells on the CPU, past the harness's look for a
chip: new configurations, mixes and metrics are found by name, a sound
run is correct, and each fault the serving cells can have, planted under
the timed path, makes ``correct`` false, at the test's size and at the
live cells' committed grids and traffic.  The lower-precision control fails
the same comparison."""
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, harness
from chipbench.bench import Spec
from chipbench.tests.cells import PEAKS, committed_tree, make_tree


@pytest.fixture
def spec(tmp_path, monkeypatch):
    # keep the process's JAX settings as the other tests expect them
    monkeypatch.setattr(harness, 'enable_cache', lambda: None)
    return Spec(tmp_path, make_tree(tmp_path))


def run(spec, cell, seed=2**40 + 11, traced=False, seconds=1.0):
    return harness.run_cell(spec, spec.cell(cell), seed=seed,
                            seconds=seconds, traced=traced,
                            t_start=time.perf_counter(), peaks=PEAKS)


@pytest.mark.parametrize('cell', ['tiny.live', 'tiny.backlog'])
def test_sound_run_is_correct_and_reports_its_metrics(spec, cell):
    result = run(spec, cell)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 0
    want = {'tiny.live': {'emit_p95_ms', 'emit_p50_ms', 'setup_s'},
            'tiny.backlog': {'frames_per_s', 'setup_s'}}[cell]
    assert set(result['metrics']) == want
    assert all(m['value'] > 0 for m in result['metrics'].values())
    assert list(result)[-1] == 'checks'
    assert set(result['checks']) == {'logprob_err_mean', 'symbols_wrong',
                                     'frames_missing'}
    json.dumps(result)


def test_traced_run_reports_per_layer_metrics_it_can_read(spec):
    result = run(spec, 'tiny.live', traced=True)
    # no device on the CPU: only the host step reads anything
    assert set(result['metrics']) == {'host_ms_per_step.live'}
    assert result['device']['window_s'] > 0
    assert 'breakdown' in result


def test_new_files_are_found_by_name(spec, tmp_path):
    bench = spec.dir
    cfg = json.loads((bench / 'configs' / 'tiny.json').read_text())
    cfg.update(name='tiny2', serving=dict(cfg['serving'], slots=4))
    (bench / 'configs' / 'tiny2.json').write_text(json.dumps(cfg))
    mix = json.loads((bench / 'traffic' / 'tiny-live.json').read_text())
    (bench / 'traffic' / 'tiny-slow.json').write_text(
        json.dumps(dict(mix, rate_per_s=5.0)))
    (bench / 'metrics' / 'streams_per_step.py').write_text(
        'def read(run):\n    return float(run.step_streams.mean())\n')
    data = json.loads((tmp_path / 'BENCHMARK.json').read_text())
    data['configs'].append({'name': 'tiny2',
                            'file': 'bench/configs/tiny2.json'})
    data['workloads'].append({'name': 'tiny2.slow', 'config': 'tiny2',
                              'traffic': 'tiny-slow', 'chips': 1})
    data['end_to_end'].append({'name': 'streams_per_step', 'unit': 'streams',
                               'workloads': ['tiny2.slow']})
    for m in data['end_to_end']:
        if m['name'] in ('emit_p95_ms', 'emit_p50_ms'):
            m['workloads'].append('tiny2.slow')
    (tmp_path / 'BENCHMARK.json').write_text(json.dumps(data))
    result = run(Spec(tmp_path, bench), 'tiny2.slow')
    assert result['correct']
    assert set(result['metrics']) == {'emit_p95_ms', 'emit_p50_ms',
                                      'setup_s', 'streams_per_step'}


def _break_state(stream_forward):
    """The chunk call returns its input state: nothing carries over."""
    def broken(cfg, params, states, frames, valid_len=None):
        lp, _ = stream_forward(cfg, params, states, frames, valid_len)
        return lp, states
    return broken


def _break_half(stream_forward):
    """Half of the slots are left out: the upper half's outputs are the
    lower half's."""
    def broken(cfg, params, states, frames, valid_len=None):
        lp, new = stream_forward(cfg, params, states, frames, valid_len)
        half = lp.shape[0] // 2
        return jnp.concatenate([lp[:half], lp[:lp.shape[0] - half]]), new
    return broken


def _break_answer(stream_forward):
    """One log-prob of every chunk is altered where it is produced."""
    def broken(cfg, params, states, frames, valid_len=None):
        lp, new = stream_forward(cfg, params, states, frames, valid_len)
        return lp.at[:, 0, 1].add(0.5), new
    return broken


@pytest.mark.parametrize('fault', [_break_state, _break_half,
                                   _break_answer])
@pytest.mark.parametrize('cell', ['tiny.live', 'tiny.backlog'])
def test_a_broken_timed_path_is_not_correct(spec, monkeypatch, fault, cell):
    from repro.models import chipmunk_net
    monkeypatch.setattr(chipmunk_net, 'stream_forward',
                        fault(chipmunk_net.stream_forward))
    result = run(spec, cell)
    assert result['correct'] is False
    assert result['failed'] > 0


def test_an_altered_symbol_is_not_correct(spec, monkeypatch):
    from repro.serving import session
    feed = session.IncrementalCTCDecoder.feed

    def altered(self, log_probs):
        fresh = feed(self, log_probs)
        if fresh:
            self.symbols[-1] += 1
        return fresh
    monkeypatch.setattr(session.IncrementalCTCDecoder, 'feed', altered)
    result = run(spec, 'tiny.live')
    assert result['correct'] is False
    assert result['checks']['symbols_wrong']['value'] > 0


@pytest.mark.parametrize('cell', ['tiny.live', 'tiny.backlog'])
def test_lower_precision_control_fails_the_comparison(spec, cell):
    """The reference computed in bfloat16, in the program's place, goes
    through a run's comparison and limits and is not correct."""
    out = control.read(spec, spec.cell(cell), 2**41 + 9)
    assert out['correct'] is False
    err = out['checks']['logprob_err_mean']
    assert err['value'] > 10 * err['limit']


@pytest.mark.parametrize('fault', [None, _break_half])
@pytest.mark.parametrize('cell,seconds', [('ctc421-c16.live', 4.0),
                                          ('ctc421-c1.live', 10.0)])
def test_half_the_grid_left_out_at_the_committed_slot_counts(
        tmp_path, monkeypatch, cell, seconds, fault):
    """At the committed grid, rate and lengths the offered load keeps
    streams in the upper half of the slots, so a chunk call that leaves
    that half out is not correct, and a sound one is."""
    from repro.models import chipmunk_net
    monkeypatch.setattr(harness, 'enable_cache', lambda: None)
    if fault is not None:
        monkeypatch.setattr(chipmunk_net, 'stream_forward',
                            fault(chipmunk_net.stream_forward))
    spec = Spec(tmp_path, committed_tree(tmp_path, cell, warmup_s=0.5))
    result = run(spec, cell, seed=2**40 + 5, seconds=seconds)
    assert result['correct'] is (fault is None)
