"""The live and backlog loops on a fake engine and a fake clock: when each
chunk is processed, how its latency is counted, and what the percentile
metrics read."""
from collections import deque

import numpy as np
import pytest

from chipbench import loop, traffic
from chipbench.bench import load_module, HERE

FRAME_S = 0.01


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class Session:
    def __init__(self, frames):
        self.frames = frames
        self.cursor = 0


class Sched:
    def __init__(self, slots):
        self.slots = [None] * slots
        self.pending = deque()
        self.done = []


class Engine:
    """Admits FIFO into free slots and advances every active stream by up
    to one chunk per step; each step takes ``cost(active)`` seconds of the
    fake clock and logs (frames due up to, step start) per advance."""

    def __init__(self, clock, slots, chunk, cost):
        self.clock, self.chunk, self.cost = clock, chunk, cost
        self.sched = Sched(slots)
        self.advances = []          # (session, new cursor, step start)

    def submit(self, frames):
        sess = Session(frames)
        self.sched.pending.append(sess)
        return sess

    def step(self):
        slots = self.sched.slots
        for i, s in enumerate(slots):
            if s is None and self.sched.pending:
                slots[i] = self.sched.pending.popleft()
        active = [(i, s) for i, s in enumerate(slots) if s is not None]
        start = self.clock()
        for i, s in active:
            s.cursor = min(len(s.frames), s.cursor + self.chunk)
            self.advances.append((s, s.cursor, start))
            if s.cursor == len(s.frames):
                self.sched.done.append(s)
                slots[i] = None
        self.clock.t += self.cost(len(active))
        return bool(active)


def _live(rate, cost, chunk=4, slots=64, seconds=3.0, seed=3):
    mix = {'rate_per_s': rate,
           'length_s': {'mean': 0.3, 'sigma': 0.5, 'min': 0.05, 'max': 1.0}}
    sched = traffic.live(mix, seed=seed, n_features=2, horizon_s=seconds + 2,
                         frame_s=FRAME_S)
    clock = Clock()
    eng = Engine(clock, slots, chunk, cost)
    em = loop.Emissions(FRAME_S)
    arrival = {}
    orig_add = em.add

    def add(sess, a, n):
        arrival[id(sess)] = a
        orig_add(sess, a, n)

    em.add = add
    window = loop.run_live(eng, sched, em, chunk=chunk, frame_s=FRAME_S,
                           warmup_s=0.5, seconds=seconds,
                           on_finish=lambda s, w: None, clock=clock,
                           sleep=clock.sleep)
    return eng, em, window, arrival


@pytest.mark.parametrize('step_s', [0.002, 0.05, 0.3])
def test_no_chunk_processed_before_its_last_frame_is_due(step_s):
    eng, em, _, arrival = _live(rate=30, cost=lambda n: step_s)
    assert eng.advances
    for sess, cursor, start in eng.advances:
        due = arrival[id(sess)] + cursor * FRAME_S
        assert start >= due - 1e-9, (start, due)
    assert em.early == 0


def test_latency_is_commit_minus_due_over_every_emission():
    step_s = 0.013
    eng, em, window, arrival = _live(rate=40, cost=lambda n: step_s)
    want = []
    for sess, cursor, start in eng.advances:
        if window.open_s <= start < window.open_s + window.seconds:
            want.append(start + step_s
                        - (arrival[id(sess)] + cursor * FRAME_S))
    got = em.all_latencies()
    assert len(got) == len(want) > 100
    np.testing.assert_allclose(np.sort(got), np.sort(want), atol=1e-9)

    class Run:
        latencies_s = got
    p95 = load_module(HERE / 'metrics' / 'emit_p95_ms.py').read(Run)
    p50 = load_module(HERE / 'metrics' / 'emit_p50_ms.py').read(Run)
    assert p95 == pytest.approx(np.percentile(want, 95) * 1e3)
    assert p50 == pytest.approx(np.percentile(want, 50) * 1e3)


def test_overrun_steps_run_back_to_back_and_show_in_latency():
    fast = _live(rate=30, cost=lambda n: 0.001)[1].all_latencies()
    slow = _live(rate=30, cost=lambda n: 0.004 * n)[1].all_latencies()
    assert np.percentile(slow, 95) > np.percentile(fast, 95)
    # a full chunk waits under one tick; a stream's short last chunk waits
    # for its stream's next tick, under two
    assert fast.max() < 2 * 4 * FRAME_S + 0.001


def test_backlog_keeps_the_queue_a_grid_deep_and_counts_partial_frames():
    clock = Clock()
    eng = Engine(clock, slots=8, chunk=4, cost=lambda n: 0.01)
    mix = {'length_s': {'median': 0.2, 'sigma': 0.5, 'min': 0.05,
                        'max': 0.5}}
    sched = traffic.backlog(mix, seed=5, n_features=2, slots=8,
                            n_streams=2000, frame_s=FRAME_S)
    em = loop.Emissions(FRAME_S)
    window = loop.run_backlog(eng, sched, em, slots=8, queue_slots=1.0,
                              warmup_steps=3, seconds=2.0,
                              on_finish=lambda s, w: None, clock=clock)
    assert min(window.pending) >= 8
    assert sum(em.step_frames) == _frames(eng, window) > 0
    assert len(em.all_latencies()) == 0


def _frames(eng, window):
    """Frames advanced by the steps inside the window, by brute force."""
    last, total = {}, 0
    for sess, cursor, start in eng.advances:
        prev = last.get(id(sess), 0)
        if start >= window.open_s:
            total += cursor - prev
        last[id(sess)] = cursor
    return total


def test_stall_watch_pins_a_blocked_call():
    import time
    from chipbench.stalls import StallWatch

    def blocked_here():
        time.sleep(0.3)

    watch = StallWatch(progress=lambda: 0)
    watch.start()
    blocked_here()
    watch.stop()
    (secs, cpu, where), = watch.longest(1)
    assert 0.2 <= secs and cpu < secs / 2
    assert 'blocked_here' in where[0]
