"""Kernel-layer microbenchmarks (CPU wall-clock of the jnp reference paths;
Pallas kernels are TPU-targeted: compiled on a TPU, interpreted elsewhere —
the mode is derived from the platform.  Interpret timings are an emulation,
but the per-step vs whole-sequence LSTM comparison is still structurally
meaningful: the per-step path pays T kernel invocations and T weight
re-streams, the sequence kernel one — the same ratio that dominates on
hardware.  Likewise the layerwise-vs-fused stack comparison: the layerwise
path pays L launches and L inter-layer hidden-sequence round-trips per
utterance, the fused wavefront one)."""
import time

import jax
import jax.numpy as jnp

from repro.core import lstm, quant
from repro.kernels.flash_attention import attention_ref
from repro.kernels.lstm_gates import lstm_gates_ref
from repro.kernels.lstm_gates import lstm_layer_fused as lstm_layer_step
from repro.kernels.lstm_seq import lstm_layer_seq
from repro.kernels.quant_matmul import quant_matmul_ref
from repro.models.layers import chunked_attention

from .common import emit, time_call


def _lstm_seq_vs_step(T: int = 128, B: int = 8):
    """The paper's CTC layer (123->421) over a T-frame utterance: old per-step
    scan path vs the persistent whole-sequence kernel (acceptance row)."""
    p = lstm.init_lstm_params(jax.random.PRNGKey(42), 123, 421)
    xs = jax.random.normal(jax.random.PRNGKey(43), (T, B, 123)) * 0.5
    tag = f'T={T} B={B} 123->421'

    f_scan = jax.jit(lambda q, x: lstm.lstm_layer(q, x)[0])
    t_scan = time_call(f_scan, p, xs, warmup=1, iters=3)
    emit('kernels/lstm_layer_xla_scan', t_scan, tag)

    f_step = jax.jit(lambda q, x: lstm_layer_step(q, x))
    t_step = time_call(f_step, p, xs, warmup=1, iters=3)
    emit('kernels/lstm_layer_pallas_step', t_step,
         f'{tag} (T kernel launches, W re-streamed per step)')

    f_seq = jax.jit(lambda q, x: lstm_layer_seq(q, x)[0])
    t_seq = time_call(f_seq, p, xs, warmup=1, iters=3)
    emit('kernels/lstm_layer_pallas_seq', t_seq,
         f'{tag} (1 launch, weight-stationary; '
         f'{t_step / t_seq:.2f}x vs per-step)')


def _lstm_stack_fused_vs_layerwise(T: int = 128):
    """The paper's full CTC stack (123->421x3) over a T-frame utterance:
    layerwise persistent kernels (one launch per layer, hidden sequence
    round-tripping between launches) vs the fused whole-stack wavefront
    kernel (one launch, inter-layer handover in scratch) — the §8
    acceptance rows.  B=8 is the packed-serving shape, B=1 the decode
    point.  The two paths are timed interleaved (like
    ``benchmarks/streaming.py``) because A-vs-B wall-clock ratios on a
    loaded 2-core host flip when one path monopolises a busy window."""
    stack = lstm.init_lstm_stack(jax.random.PRNGKey(7), 123, 421, 3)
    for B in (8, 1):
        xs = jax.random.normal(jax.random.PRNGKey(8), (T, B, 123)) * 0.5
        tag = f'T={T} B={B} 123->421x3'
        f_lw = jax.jit(
            lambda q, x: lstm.lstm_stack_apply(q, x, backend='pallas_seq')[0])
        f_fu = jax.jit(lambda q, x: lstm.lstm_stack_apply(
            q, x, backend='pallas_seq_fused')[0])
        err = float(jnp.max(jnp.abs(f_lw(stack, xs) - f_fu(stack, xs))))
        t_lw, t_fu = [], []
        for _ in range(5):                     # interleaved timing
            t0 = time.perf_counter()
            jax.block_until_ready(f_lw(stack, xs))
            t_lw.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(f_fu(stack, xs))
            t_fu.append(time.perf_counter() - t0)
        us_lw = sorted(t_lw)[len(t_lw) // 2] * 1e6
        us_fu = sorted(t_fu)[len(t_fu) // 2] * 1e6
        emit(f'kernels/lstm_stack_layerwise_seq_B{B}', us_lw,
             f'{tag} (3 launches, hidden seq round-trips between layers)')
        emit(f'kernels/lstm_stack_fused_wavefront_B{B}', us_fu,
             f'{tag} (1 launch, wavefront, inter-layer h in scratch; '
             f'{us_lw / us_fu:.2f}x vs layerwise, max_err={err:.1e})')


def _lstm_stack_quantized_fused(T: int = 32, B: int = 4):
    """int8 whole-stack wavefront vs chaining the per-layer int8 kernel, at
    a CI-friendly 48->96x3 geometry (tile=48: a 2x4-engine plan per layer).
    The fused kernel batches each diagonal's layers into ONE dot_general
    (grid D*R*C — the pre-batching kernel ran one layer per grid step,
    D*L*R*C, and measured 1.45x slower at exactly these dims), keeping the
    serial saturating hop replay per layer inside the accumulator rows.
    Both rows are bit-identical to the silicon reference scan; interpret
    timings weight per-grid-step overhead, which is what the batching
    removes."""
    from repro.core import systolic
    from repro.kernels.lstm_seq import (lstm_layer_seq_quantized,
                                        lstm_stack_seq_quantized)
    n_x, n_h, tile, L = 48, 96, 48, 3
    stack = lstm.init_lstm_stack(jax.random.PRNGKey(7), n_x, n_h, L)
    qps = []
    for l, lp in enumerate(stack.layers):
        plan = systolic.SystolicPlan(n_x if l == 0 else n_h, n_h, tile)
        qps.append(systolic.quantize_packed(systolic.pack_lstm(lp, plan)))
    xs = jax.random.normal(jax.random.PRNGKey(8), (T, B, n_x)) * 0.5
    xs_q = quant.quantize(xs, quant.STATE_FMT)
    tag = f'T={T} B={B} 48->96x3 tile=48 int8'

    def chain(x):
        h = x
        for qp in qps:
            h = lstm_layer_seq_quantized(qp, h)
        return h

    f_lw = jax.jit(chain)
    f_fu = jax.jit(lambda x: lstm_stack_seq_quantized(qps, x))
    same = bool(jnp.all(f_lw(xs_q) == f_fu(xs_q)))
    assert same, 'int8 fused stack must be bit-identical to the chain'
    t_lw, t_fu = [], []
    for _ in range(5):                     # interleaved timing
        t0 = time.perf_counter()
        jax.block_until_ready(f_lw(xs_q))
        t_lw.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(f_fu(xs_q))
        t_fu.append(time.perf_counter() - t0)
    us_lw = sorted(t_lw)[len(t_lw) // 2] * 1e6
    us_fu = sorted(t_fu)[len(t_fu) // 2] * 1e6
    emit('kernels/lstm_stack_q_layerwise_seq', us_lw,
         f'{tag} (L launches, hidden codes round-trip between layers)')
    emit('kernels/lstm_stack_q_fused_wavefront', us_fu,
         f'{tag} (1 launch, diagonal-batched D*R*C grid — L-wide '
         f'dot_general per hop, serial hop replay per layer; bit-identical '
         f'to the chain; pre-batching D*L*R*C kernel was 1.45x slower here)')


def run():
    key = jax.random.PRNGKey(0)

    # fused LSTM gates ref (123->421 paper layer)
    p = lstm.init_lstm_params(key, 123, 421)
    xh = jax.random.normal(key, (8, 123 + 421))
    w = jnp.concatenate([p.w_x, p.w_h], -1)
    c0 = jnp.zeros((8, 421))
    f = jax.jit(lstm_gates_ref)
    emit('kernels/lstm_gates_ref', time_call(f, xh, w, p.w_peep, p.b, c0),
         'B=8 123->421')

    # int8 matmul ref vs f32 matmul
    x = jax.random.normal(key, (256, 512))
    wq = jax.random.normal(jax.random.PRNGKey(1), (512, 512))
    xs, ws = quant.abs_max_scale(x, -1), quant.abs_max_scale(wq, 0)
    x_q, w_q = quant.quantize_scaled(x, xs), quant.quantize_scaled(wq, ws)
    f_int8 = jax.jit(quant_matmul_ref)
    f_f32 = jax.jit(lambda a, b: a @ b)
    emit('kernels/int8_matmul_ref', time_call(f_int8, x_q, w_q, xs, ws),
         '256x512x512')
    emit('kernels/f32_matmul', time_call(f_f32, x, wq), '256x512x512')

    # chunked flash-style attention vs naive (the prefill-path workhorse)
    B, H, S, D = 1, 8, 1024, 64
    q = jax.random.normal(key, (B, H, S, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, H, S, D))
    f_naive = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    f_chunk = jax.jit(
        lambda q, k, v: chunked_attention(q, k, v, causal=True, chunk=256))
    t_n = time_call(f_naive, q, k, v)
    t_c = time_call(f_chunk, q, k, v)
    err = float(jnp.max(jnp.abs(f_naive(q, k, v) - f_chunk(q, k, v))))
    emit('kernels/attention_naive', t_n, f'S={S}')
    emit('kernels/attention_chunked', t_c,
         f'S={S} chunk=256 max_err={err:.1e} (O(S) memory)')

    _lstm_seq_vs_step()
    _lstm_stack_fused_vs_layerwise()
    _lstm_stack_quantized_fused()
    return t_c
