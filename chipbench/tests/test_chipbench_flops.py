"""Operation and byte counts of the model, from its shapes, as the
``peephole_lstm_ctc`` model kind gives them."""
import pytest

from chipbench import flops
from chipbench.bench import HERE, load_module

CTC421 = {'inputs': 123, 'hidden': 421, 'layers': 3, 'outputs': 62}
PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


@pytest.fixture(scope='module')
def kind():
    return load_module(HERE / 'models' / 'peephole_lstm_ctc.py')


def test_weights_are_the_papers_3_8m_parameters(kind):
    assert kind.weight_bytes(CTC421) == 4 * 3_786_957


def test_flops_per_frame_counts_every_gate_product_and_the_read_out(kind):
    # layer 1: 4 gates x 421 x (123 + 421); layers 2-3: 4 x 421 x 842;
    # read-out 62 x 421; two FLOPs per multiply-add
    macs = 4 * 421 * 544 + 2 * 4 * 421 * 842 + 62 * 421
    assert kind.flops_per_frame(CTC421) == 2 * macs == 7_556_108


def test_chunk_bytes_weights_once_frames_and_state_per_stream(kind):
    base = kind.chunk_bytes(CTC421, frames=0, streams=0)
    assert base == kind.weight_bytes(CTC421)
    assert kind.chunk_bytes(CTC421, 16, 1) - base == 4 * (
        16 * (123 + 62) + 3 * 2 * 421 * 2)


def test_least_time_is_the_larger_bound(kind):
    # a full 4096 x 16 grid is bound by compute; one frame by the weights
    frames, streams = 4096 * 16, 4096
    compute = frames * 7_556_108 / 197e12
    assert flops.chunk_least_s(kind, CTC421, frames, streams, PEAKS) == \
        pytest.approx(compute)
    one = flops.chunk_least_s(kind, CTC421, 1, 1, PEAKS)
    assert one == pytest.approx(kind.chunk_bytes(CTC421, 1, 1) / 819e9)
