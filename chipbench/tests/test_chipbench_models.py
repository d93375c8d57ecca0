"""A configuration's ``model.kind`` selects ``models/<kind>.py``: the
peephole kind's weights are those the benchmark has always drawn, a second
kind runs whole cells from new files alone, the readers count its
operations, and ``frames_missing`` follows a kind's output rate."""
import hashlib
import json
import math
import time

import jax
import numpy as np
import pytest

from chipbench import check, harness, trace
from chipbench.bench import Spec
from chipbench.tests.cells import PEAKS, TINY, make_tree

# sha256 of TINY's weights at seed 2**40 + 11 (see ``_digest``), drawn by
# ``weights.make_weights`` before it moved into ``models/``
TINY_DIGEST = \
    '38c8424e8906bb47593dbe6d217f466bced21c8c9f26c4c090432b47d30e780a'

TOY_MODEL = '''\
"""Model kind ``toy``: the peephole LSTM CTC program over weights drawn by
another initialisation (normal, scaled by 1/2 sqrt(fan_in), peepholes and
biases 0) and kept flat, by name."""
import pathlib

import jax
import jax.numpy as jnp

from chipbench.bench import load_module
from chipbench.weights import seed_key

_peephole = load_module(pathlib.Path(__file__).with_name(
    'peephole_lstm_ctc.py'))
outputs = _peephole.outputs
chunk_bytes = _peephole.chunk_bytes


def flops_per_frame(model):
    return FLOPS_SCALE * _peephole.flops_per_frame(model)


FLOPS_SCALE = 1


def make_weights(model, seed):
    x, h, o = model['inputs'], model['hidden'], model['outputs']
    keys = iter(jax.random.split(seed_key(seed, 1), 2 * model['layers'] + 1))
    w = {}
    for n in range(model['layers']):
        fan_in = x if n == 0 else h
        w[f'{n}.w_x'] = jax.random.normal(next(keys), (4, h, fan_in)) \\
            / (2 * fan_in ** 0.5)
        w[f'{n}.w_h'] = jax.random.normal(next(keys), (4, h, h)) \\
            / (2 * h ** 0.5)
        w[f'{n}.w_peep'] = jnp.zeros((3, h))
        w[f'{n}.b'] = jnp.zeros((4, h))
    w['w_out'] = jax.random.normal(next(keys), (o, h)) / (2 * h ** 0.5)
    w['b_out'] = jnp.zeros((o,))
    return w


def nested(w):
    layers = sorted({k.split('.')[0] for k in w if '.' in k}, key=int)
    return {'layers': tuple({p: w[f'{n}.{p}'] for p in
                             ('w_x', 'w_h', 'w_peep', 'b')} for n in layers),
            'w_out': w['w_out'], 'b_out': w['b_out']}


def program(config, weights):
    return _peephole.program(config, nested(weights))
'''

TOY_REFERENCE = '''\
"""Reference of model kind ``toy``: its flat weights, nested, through the
peephole LSTM CTC reference."""
import pathlib

from chipbench.bench import load_module

_here = pathlib.Path(__file__).parent
_toy = load_module(_here.parent / 'models' / 'toy.py')
_peephole = load_module(_here / 'peephole_lstm_ctc.py')


def log_probs(weights, frames, **kw):
    return _peephole.log_probs(_toy.nested(weights), frames, **kw)
'''


def _digest(weights) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(weights)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture
def spec(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, 'enable_cache', lambda: None)
    return Spec(tmp_path, make_tree(tmp_path))


def _files(root):
    return {p: p.read_bytes() for p in root.rglob('*') if p.is_file()}


TOY = dict(TINY, name='toy', reference='toy',
           model=dict(TINY['model'], kind='toy'))


def _write_toy_kind(bench, flops_scale=1):
    (bench / 'models' / 'toy.py').write_text(
        TOY_MODEL.replace('FLOPS_SCALE = 1', f'FLOPS_SCALE = {flops_scale}'))


def _add_toy(spec):
    """Files and entries of the ``toy`` kind and its cells ``toy.live``
    and ``toy.backlog``, next to the tree's own."""
    bench = spec.dir
    _write_toy_kind(bench)
    (bench / 'reference' / 'toy.py').write_text(TOY_REFERENCE)
    (bench / 'configs' / 'toy.json').write_text(json.dumps(TOY))
    path = spec.root / 'BENCHMARK.json'
    data = json.loads(path.read_text())
    data['configs'].append({'name': 'toy', 'file': 'bench/configs/toy.json'})
    for mix in ('live', 'backlog'):
        data['workloads'].append({'name': f'toy.{mix}', 'config': 'toy',
                                  'traffic': f'tiny-{mix}', 'chips': 1})
    for m in data['end_to_end'] + data['per_layer']:
        m.get('workloads', []).extend(
            w.replace('tiny.', 'toy.') for w in list(m.get('workloads', [])))
    path.write_text(json.dumps(data))
    return Spec(spec.root, bench)


def test_peephole_weights_are_bit_identical_to_the_old_draw(spec):
    kind = spec.model(TINY)
    assert _digest(kind.make_weights(TINY['model'], 2**40 + 11)) == \
        TINY_DIGEST


@pytest.mark.parametrize('cell', ['toy.live', 'toy.backlog'])
def test_a_second_kind_runs_from_new_files_alone(spec, cell):
    before = _files(spec.root)
    before.pop(spec.root / 'BENCHMARK.json')
    spec = _add_toy(spec)
    w = spec.model(TOY).make_weights(TOY['model'], 3)
    assert not np.array_equal(
        w['w_out'], spec.model(TINY).make_weights(TINY['model'], 3)['w_out'])
    result = harness.run_cell(spec, spec.cell(cell), seed=2**40 + 13,
                              seconds=1.0, traced=False,
                              t_start=time.perf_counter(), peaks=PEAKS)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 0
    after = _files(spec.root)
    assert all(after[p] == data for p, data in before.items())


def test_readers_count_the_work_through_the_kind(spec):
    """A wrong count in ``toy.py`` moves what the per-layer readers compute
    from one fixed run."""
    iv = np.array([[1.0, 2.0], [4.0, 5.0]])
    dev = trace.Device(['fusion.1'] * 2, iv, ['jit_fwd(1)'] * 2, iv, iv)
    tr = trace.Trace({'/device:TPU:0': dev},
                     {'chipbench.window': np.array([[0.5, 9.5]]),
                      'chipbench.step': np.array([[0.8, 3.0], [3.5, 5.5]])})

    def read(metric, flops_scale):
        _write_toy_kind(spec.dir, flops_scale)
        run = harness.Run(
            setup_s=1.0, window=None, latencies_s=np.zeros(0),
            step_frames=np.array([4096 * 16] * 2),
            step_streams=np.array([4096] * 2), model=TOY['model'],
            kind=spec.model(TOY), serving=TOY['serving'], peaks=PEAKS,
            trace=tr)
        return spec.reader(metric)(run)

    assert read('mfu_pct.backlog', 2) == pytest.approx(
        2 * read('mfu_pct.backlog', 1))
    assert read('chunk_roofline', 3) > read('chunk_roofline', 1)


def test_frames_missing_counts_the_kinds_output_rows():
    """A kind whose output rate is half its input rate: a stream of 9
    frames is due 5 rows."""
    frames = np.zeros((9, 13), np.float32)
    ref = np.log(np.full((1, 5, 16), 1 / 16, np.float32))
    half = lambda n: math.ceil(n / 2)
    whole = check.numbers([(frames, ref[0], None)], ref, half)
    short = check.numbers([(frames, ref[0, :4], None)], ref, half)
    assert whole['frames_missing'] == 0 and whole['logprob_err'] == 0
    assert short['frames_missing'] == 1


def test_an_unknown_kind_fails_naming_its_missing_file(spec):
    cfg = json.loads((spec.dir / 'configs' / 'tiny.json').read_text())
    cfg['model']['kind'] = 'no_such_kind'
    (spec.dir / 'configs' / 'tiny.json').write_text(json.dumps(cfg))
    with pytest.raises(FileNotFoundError,
                       match=r'models/no_such_kind\.py'):
        harness.run_cell(spec, spec.cell('tiny.live'), seed=1, seconds=1.0,
                         traced=False, t_start=time.perf_counter(),
                         peaks=PEAKS)
