"""The plain reference against the program's own whole-sequence forward
pass, on the CPU at a small size (float32 throughout there)."""
import jax.numpy as jnp
import numpy as np

from chipbench.bench import HERE, Spec, load_module

MODEL = {'kind': 'peephole_lstm_ctc', 'inputs': 13, 'hidden': 32,
         'layers': 3, 'outputs': 16}
KIND = Spec(HERE.parent).model({'model': MODEL})
make_weights = KIND.make_weights


def test_reference_matches_the_programs_forward_pass():
    from repro.models import chipmunk_net
    ref = load_module(HERE / 'reference' / 'peephole_lstm_ctc.py')
    w = make_weights(MODEL, 2**35 + 1)
    cfg, params = KIND.program({
        'name': 't', 'model': MODEL, 'dtype': 'float32',
        'lstm_backend': 'xla_scan'}, w)
    assert 4 * params.num_params() == KIND.weight_bytes(MODEL)
    x = np.random.default_rng(1).standard_normal((3, 40, 13), np.float32)
    want = np.moveaxis(np.asarray(chipmunk_net.forward(cfg, params,
                                                       jnp.asarray(x))), 0, 1)
    got = np.asarray(ref.log_probs(w, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_weights_come_from_the_seed():
    a = make_weights(MODEL, 2**33 + 5)
    b = make_weights(MODEL, 2**33 + 5)
    c = make_weights(MODEL, 5)
    assert np.array_equal(a['w_out'], b['w_out'])
    assert not np.array_equal(a['w_out'], c['w_out'])


def test_products_precision_and_the_control_rank_as_stated():
    """Operands rounded to bfloat16 move the log-probs a little; the
    control, computing in bfloat16 throughout, moves them more; an unknown
    precision is refused."""
    import pytest
    ref = load_module(HERE / 'reference' / 'peephole_lstm_ctc.py')
    w = make_weights(MODEL, 2**36 + 3)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (4, 50, 13), np.float32))
    hi = np.asarray(ref.log_probs(w, x))
    bf_ops = np.asarray(ref.log_probs(w, x, products='bf16_operands'))
    ctl = np.asarray(ref.log_probs(w, x, dtype=jnp.bfloat16))
    assert ctl.dtype == np.float32
    assert 0 < np.abs(bf_ops - hi).max() < np.abs(ctl - hi).max()
    with pytest.raises(ValueError):
        ref.log_probs(w, x, products='fast')
