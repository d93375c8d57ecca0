"""Weights of a peephole-LSTM CTC model, made on the device from the seed.

One jitted call makes every array in float32, laid out as the plain
reference reads them (see ``reference/peephole_lstm_ctc.py``).  The scales
follow the usual uniform initialisation: +-1/sqrt(fan_in) for the matrices,
+-0.1 for the diagonal peepholes, a forget-gate bias of 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (two 32-bit words) and a stream."""
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(words), stream)


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
    """``model`` holds ``inputs``, ``hidden``, ``layers`` and ``outputs``."""
    return _make(seed_key(seed, 0), inputs=model['inputs'],
                 hidden=model['hidden'], layers=model['layers'],
                 outputs=model['outputs'])

