"""A small benchmark tree for CPU tests: the real metrics, references,
model kinds and peaks, a model far below the published widths, and short
mixes."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

TINY = {
    'name': 'tiny',
    'source': 'test',
    'model': {'kind': 'peephole_lstm_ctc', 'inputs': 13, 'hidden': 32,
              'layers': 2, 'outputs': 16},
    'dtype': 'float32', 'products': 'highest', 'lstm_backend': 'auto',
    'reference': 'peephole_lstm_ctc',
    'serving': {'slots': 8, 'chunk': 4, 'frame_ms': 10, 'decode_ctc': True,
                'async_dispatch': False},
    'limits': {'logprob_err_mean': 1e-5, 'symbols_wrong': 0,
               'frames_missing': 0},
}
LIVE = {'kind': 'live', 'rate_per_s': 20.0,
        'length_s': {'dist': 'lognormal', 'mean': 0.3, 'sigma': 0.5,
                     'min': 0.1, 'max': 0.6},
        'warmup_s': 0.3, 'sample_streams': 6}
BACKLOG = {'kind': 'backlog',
           'length_s': {'dist': 'lognormal', 'median': 0.2, 'sigma': 0.5,
                        'min': 0.05, 'max': 0.5},
           'queue_slots': 1.0, 'warmup_steps': 2, 'max_frames_per_s': 20000,
           'sample_streams': 6}
PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


def make_tree(root: pathlib.Path) -> pathlib.Path:
    """``root/BENCHMARK.json`` with cells ``tiny.live`` and
    ``tiny.backlog``; returns the benchmark directory under it."""
    bench = root / 'bench'
    for sub in ('metrics', 'reference', 'models'):
        shutil.copytree(BENCH / sub, bench / sub,
                        ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(BENCH / 'peaks.json', bench / 'peaks.json')
    (bench / 'configs').mkdir()
    (bench / 'traffic').mkdir()
    (bench / 'configs' / 'tiny.json').write_text(json.dumps(TINY))
    (bench / 'traffic' / 'tiny-live.json').write_text(json.dumps(LIVE))
    (bench / 'traffic' / 'tiny-backlog.json').write_text(json.dumps(BACKLOG))
    real = json.loads((BENCH.parent / 'BENCHMARK.json').read_text())
    spec = {
        'run_seconds': real['run_seconds'],
        'configs': [{'name': 'tiny', 'file': 'bench/configs/tiny.json'}],
        'workloads': [
            {'name': 'tiny.live', 'config': 'tiny', 'traffic': 'tiny-live',
             'chips': 1},
            {'name': 'tiny.backlog', 'config': 'tiny',
             'traffic': 'tiny-backlog', 'chips': 1}],
        'end_to_end': _rename(real['end_to_end']),
        'per_layer': _rename(real['per_layer']),
    }
    (root / 'BENCHMARK.json').write_text(json.dumps(spec))
    return bench


def _rename(metrics: list) -> list:
    """The real metrics, their cell lists pointed at the tiny cells."""
    out = []
    for m in metrics:
        m = dict(m)
        if 'workloads' in m:
            m['workloads'] = [w.replace('ctc421-c16.', 'tiny.')
                              .replace('ctc421-c1.', 'tiny.')
                              for w in m['workloads']]
        out.append(m)
    return out


def committed_tree(root: pathlib.Path, cell_name: str,
                   warmup_s: float) -> pathlib.Path:
    """``root/BENCHMARK.json`` with the one committed cell ``cell_name``:
    its grid, chunk and traffic (rate, lengths, sample) as committed, the
    model cut to ``TINY``'s and run by the XLA scan in exact float32 with
    ``TINY``'s limits, and the warm-up cut to ``warmup_s``.  Slot
    occupancy depends on the traffic and the grid, not on the model's
    width."""
    real = json.loads((BENCH.parent / 'BENCHMARK.json').read_text())
    cell = next(c for c in real['workloads'] if c['name'] == cell_name)
    entry = next(c for c in real['configs'] if c['name'] == cell['config'])
    config = json.loads((BENCH.parent / entry['file']).read_text())
    config.update({k: TINY[k] for k in ('model', 'products', 'limits')},
                  lstm_backend='xla_scan')
    mix = json.loads(
        (BENCH / 'traffic' / f'{cell["traffic"]}.json').read_text())
    mix['warmup_s'] = warmup_s
    bench = root / 'bench'
    for sub in ('metrics', 'reference', 'models'):
        shutil.copytree(BENCH / sub, bench / sub,
                        ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(BENCH / 'peaks.json', bench / 'peaks.json')
    (bench / 'configs').mkdir()
    (bench / 'traffic').mkdir()
    (bench / 'configs' / 'cfg.json').write_text(json.dumps(config))
    (bench / 'traffic' / f'{cell["traffic"]}.json').write_text(
        json.dumps(mix))
    spec = dict(real, configs=[{'name': cell['config'],
                                'file': 'bench/configs/cfg.json'}],
                workloads=[cell])
    (root / 'BENCHMARK.json').write_text(json.dumps(spec))
    return bench
