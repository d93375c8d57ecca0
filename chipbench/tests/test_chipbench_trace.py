"""The reduction from a profiler trace to busy, idle and per-operation
time, on hand-made intervals."""
import numpy as np
import pytest

from chipbench import trace
from chipbench.bench import HERE, load_module


def _trace():
    ops = ['fusion.1', 'copy.2', 'fusion.1', 'scatter.3']
    op_iv = np.array([[1.0, 2.0], [1.5, 2.5], [4.0, 5.0], [7.0, 7.5]])
    mods = ['jit_fwd(1)', 'jit_fwd(1)', 'jit_scatter(9)']
    mod_iv = np.array([[1.0, 2.5], [4.0, 5.0], [7.0, 7.5]])
    dev = trace.Device(ops, op_iv, mods, mod_iv, trace.union(op_iv))
    spans = {
        'chipbench.window': np.array([[0.5, 9.5]]),
        'chipbench.step': np.array([[0.8, 3.0], [3.5, 5.5], [6.5, 8.0]]),
        'chipbench.tick_wait': np.array([[5.6, 6.4], [8.1, 9.4]]),
    }
    return trace.Trace({'/device:TPU:0': dev}, spans)


def test_union_merges_overlaps_in_any_order():
    iv = np.array([[5, 6], [0, 1], [0.5, 2], [6, 7], [3, 4], [3.5, 3.6]])
    np.testing.assert_array_equal(trace.union(iv),
                                  [[0, 2], [3, 4], [5, 7]])
    assert trace.union(np.zeros((0, 2))).shape == (0, 2)


def test_busy_is_the_union_inside_the_window():
    t = _trace()
    assert t.window() == (0.5, 9.5)
    assert t.busy_s() == pytest.approx(1.5 + 1.0 + 0.5)
    assert t.busy_in(t.span_iv('step')) == pytest.approx(3.0)
    assert t.module_s('jit_fwd') == pytest.approx(2.5)


def test_top_ops_sum_by_name():
    assert _trace().top_ops() == [['fusion.1', pytest.approx(2.0)],
                                  ['copy.2', pytest.approx(1.0)],
                                  ['scatter.3', pytest.approx(0.5)]]


def test_idle_gaps_are_named_for_the_span_that_covers_them():
    gaps = _trace().idle_gaps()
    assert gaps[0] == ['step', pytest.approx(2.0)]           # 5.0 - 7.0
    assert gaps[1] == ['tick_wait', pytest.approx(2.0)]      # 7.5 - 9.5
    assert gaps[2] == ['step', pytest.approx(1.5)]           # 2.5 - 4.0
    assert sum(g[1] for g in gaps) == pytest.approx(9.0 - 3.0)


def test_per_layer_readers_on_the_hand_made_trace():
    t = _trace()

    class Run:
        trace = t
        step_frames = np.array([64, 64, 64])
        step_streams = np.array([4, 4, 4])
        model = {'inputs': 123, 'hidden': 421, 'layers': 3, 'outputs': 62}
        kind = load_module(HERE / 'models' / 'peephole_lstm_ctc.py')
        peaks = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}

    read = lambda name: load_module(HERE / 'metrics' / f'{name}.py').read(Run)
    assert read('idle_pct.backlog') == pytest.approx((1 - 3.0 / 9.0) * 100)
    assert read('host_ms_per_step.live') == pytest.approx(
        (2.2 + 2.0 + 1.5 - 3.0) / 3 * 1e3)
    assert read('chunk_device_ms.live') == pytest.approx(2.5 / 3 * 1e3)
    assert read('mfu_pct.backlog') == pytest.approx(
        192 * 7_556_108 / (9.0 * 197e12) * 100)
    assert 0 < read('chunk_roofline') < 100
