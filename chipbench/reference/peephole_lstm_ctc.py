"""Plain reference: a stack of unidirectional peephole LSTM layers with a
linear CTC read-out and log-softmax (Graves et al. 2013, Eqs. 1-5), written
from the equations in ``jax.numpy``.  It reads weight arrays only and shares
no code with the system under test.

    i_t = sigmoid(W_xi x_t + W_hi h_{t-1} + w_ci * c_{t-1} + b_i)
    f_t = sigmoid(W_xf x_t + W_hf h_{t-1} + w_cf * c_{t-1} + b_f)
    c_t = f_t * c_{t-1} + i_t * tanh(W_xc x_t + W_hc h_{t-1} + b_c)
    o_t = sigmoid(W_xo x_t + W_ho h_{t-1} + w_co * c_t + b_o)
    h_t = o_t * tanh(c_t)
    y_t = log_softmax(W_hy h_t + b_y)          (last layer's h only)

Weights, as ``weights.make_weights`` lays them out: per layer ``w_x``
(4, H, X), ``w_h`` (4, H, H) in gate order (i, f, c, o), ``w_peep`` (3, H)
for (i, f, o) and ``b`` (4, H); then ``w_out`` (O, H) and ``b_out`` (O,).

``log_probs`` is the reference, in float32.  ``products`` is the precision
of its matrix products, as a configuration states it: ``'highest'``
(exact float32 products) or ``'bf16_operands'`` (each product's operands
rounded to bfloat16, accumulated in float32: one pass of a TPU's matrix
unit, XLA's default for float32 there).  ``dtype=bfloat16`` is the
lower-precision control: weights, inputs, state, every product's result
and the gate arithmetic in bfloat16; the read-out accumulates in float32
and the log-softmax is float32, so the control's output is not rounded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _dot(spec, a, b, products, dtype):
    if products == 'highest':
        out = jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    elif products == 'bf16_operands':
        out = jnp.einsum(spec, a.astype(jnp.bfloat16),
                         b.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
    else:
        raise ValueError(f'unknown products precision {products!r}')
    return out.astype(dtype)


def _cell(layer, pre_x, h, c, products):
    pre = pre_x + _dot('ghk,bk->bgh', layer['w_h'], h, products,
                       h.dtype) + layer['b']
    i = jax.nn.sigmoid(pre[:, 0] + layer['w_peep'][0] * c)
    f = jax.nn.sigmoid(pre[:, 1] + layer['w_peep'][1] * c)
    c = f * c + i * jnp.tanh(pre[:, 2])
    o = jax.nn.sigmoid(pre[:, 3] + layer['w_peep'][2] * c)
    return o * jnp.tanh(c), c


@functools.partial(jax.jit, static_argnames=('products', 'dtype'))
def log_probs(weights, frames, products='highest', dtype=jnp.float32):
    """frames (B, T, X) from zero state -> log-probs (B, T, O), returned as
    float32."""
    if dtype != jnp.float32:
        products = 'bf16_operands'
    w = jax.tree.map(lambda a: a.astype(dtype), weights)
    layers = w['layers']
    xs = jnp.moveaxis(frames.astype(dtype), 0, 1)           # (T, B, X)
    pre_x0 = _dot('ghx,tbx->tbgh', layers[0]['w_x'], xs, products, dtype)
    batch, hidden = frames.shape[0], layers[0]['w_h'].shape[-1]
    zeros = jnp.zeros((batch, hidden), dtype)

    def step(carry, px0):
        new, below = [], None
        for n, (layer, (h, c)) in enumerate(zip(layers, carry)):
            px = px0 if n == 0 else _dot('ghx,bx->bgh', layer['w_x'], below,
                                         products, dtype)
            h, c = _cell(layer, px, h, c, products)
            new.append((h, c))
            below = h
        return tuple(new), below

    _, hs = lax.scan(step, tuple((zeros, zeros) for _ in layers), pre_x0)
    ys = _dot('oh,tbh->tbo', w['w_out'], hs, products,
              jnp.float32) + w['b_out'].astype(jnp.float32)
    return jnp.moveaxis(jax.nn.log_softmax(ys, axis=-1), 0, 1)
