"""Model kind ``peephole_lstm_ctc``: a stack of peephole LSTM layers with a
linear CTC read-out (Graves et al. 2013), one output row per input frame.
``model`` holds ``inputs``, ``hidden``, ``layers`` and ``outputs``.

Weights are made on the device from the seed in one jitted call, every
array in float32, laid out as ``reference/peephole_lstm_ctc.py`` reads
them.  The scales follow the usual uniform initialisation: +-1/sqrt(fan_in)
for the matrices, +-0.1 for the diagonal peepholes, a forget-gate bias of 1.

FLOPs count the matrix products (two per multiply-add): per frame, each
layer's input and recurrent products for four gates, and the read-out.
The element-wise gate arithmetic (about 20 operations per hidden unit and
layer, 0.3% of the products at 421 hidden) is left out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import seed_key


@functools.partial(jax.jit, static_argnames=('inputs', 'hidden', 'layers',
                                             'outputs'))
def _make(key, *, inputs, hidden, layers, outputs):
    keys = jax.random.split(key, 3 * layers + 1)
    out = []
    for n in range(layers):
        fan_in = inputs if n == 0 else hidden
        kx, kh, kp = keys[3 * n:3 * n + 3]
        out.append({
            'w_x': jax.random.uniform(kx, (4, hidden, fan_in), jnp.float32,
                                      -1, 1) / np.sqrt(fan_in),
            'w_h': jax.random.uniform(kh, (4, hidden, hidden), jnp.float32,
                                      -1, 1) / np.sqrt(hidden),
            'w_peep': jax.random.uniform(kp, (3, hidden), jnp.float32,
                                         -0.1, 0.1),
            'b': jnp.zeros((4, hidden), jnp.float32).at[1].set(1.0),
        })
    w_out = jax.random.uniform(keys[-1], (outputs, hidden), jnp.float32,
                               -1, 1) / np.sqrt(hidden)
    return {'layers': tuple(out), 'w_out': w_out,
            'b_out': jnp.zeros((outputs,), jnp.float32)}


def make_weights(model: dict, seed: int) -> dict:
    return _make(seed_key(seed, 0), inputs=model['inputs'],
                 hidden=model['hidden'], layers=model['layers'],
                 outputs=model['outputs'])


def program(config: dict, weights: dict):
    """The program's ``ArchConfig`` and parameter structure, over the
    benchmark's weights."""
    from repro.configs import ArchConfig
    from repro.core.lstm import LSTMParams, LSTMStackParams
    m = config['model']
    arch = ArchConfig(
        name=config['name'], family='lstm', n_layers=m['layers'],
        d_model=m['hidden'], n_heads=1, n_kv_heads=1, d_ff=0,
        vocab_size=m['outputs'], lstm_hidden=m['hidden'],
        lstm_inputs=m['inputs'], n_outputs=m['outputs'],
        param_dtype=config['dtype'], activation_dtype=config['dtype'],
        remat='none', lstm_backend=config['lstm_backend'])
    params = LSTMStackParams(
        tuple(LSTMParams(l['w_x'], l['w_h'], l['w_peep'], l['b'])
              for l in weights['layers']),
        weights['w_out'], weights['b_out'])
    return arch, params


def outputs(frames: int) -> int:
    return frames


def flops_per_frame(model: dict) -> int:
    x, h, n, o = (model[k] for k in ('inputs', 'hidden', 'layers', 'outputs'))
    macs = 4 * h * (x + h) + (n - 1) * 4 * h * (2 * h) + o * h
    return 2 * macs


def weight_bytes(model: dict, itemsize: int = 4) -> int:
    x, h, n, o = (model[k] for k in ('inputs', 'hidden', 'layers', 'outputs'))
    per_layer = lambda fan_in: 4 * h * fan_in + 4 * h * h + 3 * h + 4 * h
    return itemsize * (per_layer(x) + (n - 1) * per_layer(h) + o * h + o)


def chunk_bytes(model: dict, frames: int, streams: int,
                itemsize: int = 4) -> int:
    """The least HBM traffic of one chunk call: the weights once, each
    valid frame's input and log-probs, and each active stream's (h, c) per
    layer read and written."""
    x, h, n, o = (model[k] for k in ('inputs', 'hidden', 'layers', 'outputs'))
    return (weight_bytes(model, itemsize)
            + itemsize * frames * (x + o)
            + itemsize * streams * n * 2 * h * 2)
