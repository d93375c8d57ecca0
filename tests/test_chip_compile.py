"""Compile-only checks for a TPU v5e: the served path's kernels at real width.

Nothing here runs a kernel.  Each test compiles for a v5e chip that is
described, not attached (``jax.experimental.topologies``), at the paper's
widths (123 -> 421 hidden, T=16, 8 serving slots), so a block shape, layout
or VMEM budget that Mosaic refuses fails here at no chip time.  The topology
is described inside a fixture, never at import: only one process may load
the TPU library, and the worker given this file is the one that does.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import configs
from repro.core import systolic
from repro.core.lstm import (_VMEM_BUDGET_BYTES, init_lstm_params,
                             init_lstm_stack)
from repro.kernels.lstm_seq import (lstm_layer_seq, lstm_layer_seq_quantized,
                                    lstm_stack_seq, lstm_stack_seq_quantized,
                                    stack_vmem_bytes_estimate)
from repro.models import get_bundle
from repro.serving import StreamingEngine

N_X, N_H, T, B = 123, 421, 16, 8


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform='tpu',
                                           topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    finally:
        jax.config.update('jax_enable_compilation_cache', was)


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Dispatch as on a TPU (auto selection, interpret mode off) while the
    process's devices stay CPUs."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize('masked', [True, False], ids=['masked', 'unmasked'])
def test_lstm_layer_seq_compiles(one_chip, masked):
    p = _sds(jax.eval_shape(
        lambda: init_lstm_params(jax.random.PRNGKey(0), N_X, N_H)), one_chip)
    xs = jax.ShapeDtypeStruct((T, B, N_X), jnp.float32, sharding=one_chip)
    vl = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def fn(p, xs, vl):
        return lstm_layer_seq(p, xs, valid_len=vl if masked else None,
                              interpret=False)

    assert 'tpu_custom_call' in _compile_text(fn, p, xs, vl)


def test_engine_chunk_call_compiles(one_chip, on_tpu):
    """The jitted chunk call of the serving engine for the full chipmunk-ctc
    model, on the backend ``auto`` resolves to on a TPU."""
    cfg = configs.get_config('chipmunk-ctc')
    params = jax.eval_shape(
        lambda: get_bundle(cfg).init(jax.random.PRNGKey(0))[0])
    eng = StreamingEngine(cfg, params, max_streams=B, chunk=T)
    assert eng.backend == 'pallas_seq'
    compiled = eng._fwd.lower(
        _sds(params, one_chip), _sds(eng.states, one_chip),
        jax.ShapeDtypeStruct((B, T, N_X), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)).compile()
    assert 'tpu_custom_call' in compiled.as_text()


def _quantized_layers(n_layers):
    qps = []
    for l in range(n_layers):
        n_x = N_X if l == 0 else N_H
        p = init_lstm_params(jax.random.PRNGKey(l), n_x, N_H)
        qps.append(systolic.quantize_packed(systolic.pack_lstm(
            p, systolic.SystolicPlan(n_x, N_H, systolic.N_LSTM_SILICON))))
    return qps


@pytest.mark.parametrize('stack', [False, True], ids=['layer', 'stack3'])
def test_int8_seq_kernels_compile(one_chip, stack):
    """The bit-accurate int8 kernels on the chip's 96-row tiles."""
    qps = _quantized_layers(3 if stack else 1)
    arrays = [_sds(tuple(qp[:5]), one_chip) for qp in qps]
    xs = jax.ShapeDtypeStruct((T, B, N_X), jnp.int8, sharding=one_chip)
    vl = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def fn(arrays, xs, vl):
        packs = [systolic.QuantizedPackedLSTM(*a, qp.plan_shape)
                 for a, qp in zip(arrays, qps)]
        if stack:
            return lstm_stack_seq_quantized(packs, xs, valid_len=vl,
                                            interpret=False)
        return lstm_layer_seq_quantized(packs[0], xs, valid_len=vl,
                                        interpret=False)

    assert 'tpu_custom_call' in _compile_text(fn, arrays, xs, vl)


def test_fused_stack_compiles_at_admitted_width(one_chip):
    """The f32 wavefront stack at the widest hidden size stack selection
    admits below 421 (3 x 256: its resident set fits the VMEM budget)."""
    n_h = 256
    assert stack_vmem_bytes_estimate(N_X, n_h, 3, B) <= _VMEM_BUDGET_BYTES
    params = _sds(jax.eval_shape(
        lambda: init_lstm_stack(jax.random.PRNGKey(0), N_X, n_h, 3, 62)),
        one_chip)
    xs = jax.ShapeDtypeStruct((T, B, N_X), jnp.float32, sharding=one_chip)
    vl = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def fn(p, xs, vl):
        return lstm_stack_seq(p, xs, valid_len=vl, interpret=False)[0]

    assert 'tpu_custom_call' in _compile_text(fn, params, xs, vl)


def test_systolic_2x2_chunk_call_compiles(topo):
    """The §6 scale-out of the served model over the 2x2 preset's geometry
    on four described chips: one program with the row/col collectives."""
    cfg = configs.get_config('chipmunk-ctc')
    mesh = systolic.make_systolic_mesh(2, 2, devices=topo.devices)
    systolic.install_mesh(mesh)
    try:
        params = jax.eval_shape(
            lambda: get_bundle(cfg).init(jax.random.PRNGKey(0))[0])
        eng = StreamingEngine(cfg, params, max_streams=B, chunk=T)
        assert eng.backend == 'pallas_seq_systolic'
        repl = NamedSharding(mesh, P())
        text = eng._fwd.lower(
            _sds(params, repl), _sds(eng.states, repl),
            jax.ShapeDtypeStruct((B, T, N_X), jnp.float32, sharding=repl),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=repl)
        ).compile().as_text()
    finally:
        systolic.clear_mesh()
    assert 'all-reduce' in text and 'all-gather' in text
