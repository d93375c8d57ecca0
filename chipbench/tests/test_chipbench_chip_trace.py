"""The trace reduction on a trace recorded on the chip: one traced second
of ``ctc421-c16.live`` on a TPU v5 lite (``data/c16-live.xplane.pb.gz``)
and the result line that run printed (``data/c16-live.json``).  The
reduction has to give the same busy time, window, breakdown and per-layer
metrics again, and its busy time has to agree with a plain sweep over the
raw events."""
import gzip
import json
import pathlib

import pytest

from chipbench import trace
from chipbench.bench import HERE, load_module

DATA = pathlib.Path(__file__).parent / 'data'


@pytest.fixture(scope='module')
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp('trace') / 'c16-live.xplane.pb'
    path.write_bytes(gzip.decompress(
        (DATA / 'c16-live.xplane.pb.gz').read_bytes()))
    return path, json.loads((DATA / 'c16-live.json').read_text())


def test_totals_match_the_recorded_run(recorded):
    path, want = recorded
    t = trace.load(path)
    assert list(t.devices) == ['/device:TPU:0']
    lo, hi = t.window()
    assert hi - lo == pytest.approx(want['device']['window_s'], rel=1e-12)
    assert t.busy_s() == pytest.approx(want['device']['busy_s'], rel=1e-12)
    got = {'device_ops': t.top_ops(), 'idle_gaps': t.idle_gaps()}
    assert json.loads(json.dumps(got)) == want['breakdown']


def test_per_layer_metrics_match_the_recorded_run(recorded):
    path, want = recorded

    class Run:
        trace = trace.load(path)

    for name in ('host_ms_per_step.live', 'chunk_device_ms.live'):
        value = load_module(HERE / 'metrics' / f'{name}.py').read(Run)
        assert value == pytest.approx(want['metrics'][name]['value'],
                                      rel=1e-12)


def test_busy_time_agrees_with_a_plain_sweep_over_the_raw_events(recorded):
    from jax.profiler import ProfileData
    path, want = recorded
    data = ProfileData.from_file(str(path))
    window = ops = None
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == 'chipbench.window':
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
            if plane.name == '/device:TPU:0' and line.name == 'XLA Ops':
                ops = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                             for ev in line.events)
    lo, hi = window
    busy, reach = 0.0, lo
    for s, e in ops:
        s, e = max(s, reach), min(e, hi)
        if e > s:
            busy += e - s
            reach = e
    assert busy * 1e-9 == pytest.approx(want['device']['busy_s'], rel=1e-9)
    assert 0 < busy * 1e-9 < (hi - lo) * 1e-9
