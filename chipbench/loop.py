"""The measured loops: streams in through the engine's public API, chunk
emissions out, on the host clock.

``live``: the engine steps once per chunk period (a tick).  A stream is
submitted at the first tick after its first chunk has fully arrived, and
each step advances a stream by at most one chunk, so no chunk is processed
before its last frame is due; a stream falls behind only when steps
overrun the tick or no slot is free.  When a step ends after the next tick,
the next step starts at once.

``backlog``: steps run back to back, and the queue is kept topped up.

Both loops run a warm-up on the mix's own traffic first, then the window.
Every host phase sits in a ``chipbench.*`` trace span, so a traced run can
say what the host was doing while the device sat idle.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, List, Optional

import jax
import numpy as np

SPAN = 'chipbench.'


def span(name: str):
    return jax.profiler.TraceAnnotation(SPAN + name)


class Emissions:
    """Tracks every submitted stream's cursor and turns cursor moves into
    chunk emissions.  A chunk that ends at frame ``c`` of a stream that
    arrived at ``a`` was due at ``a + c * frame_s``; its latency runs from
    then to the end of the ``step()`` that committed it."""

    def __init__(self, frame_s: float):
        self.frame_s = frame_s
        self.sessions: list = []
        self.arrival = np.zeros(0)
        self.length = np.zeros(0, np.int64)
        self.last = np.zeros(0, np.int64)
        self._new: list = []
        self.latencies: List[np.ndarray] = []
        self.step_frames: List[int] = []
        self.step_streams: List[int] = []
        self.early = 0

    def add(self, session, arrival_s: float, length: int) -> None:
        self._new.append((session, arrival_s, length, session.cursor))

    def _merge(self) -> None:
        if self._new:
            sess, arrival, length, cursor = zip(*self._new)
            self.sessions.extend(sess)
            self.arrival = np.concatenate([self.arrival, arrival])
            self.length = np.concatenate([self.length, length])
            self.last = np.concatenate([self.last, cursor]).astype(np.int64)
            self._new = []

    def after_step(self, t_start: float, t_end: float, record: bool) -> list:
        """Account one ``step()`` that ran over [t_start, t_end] (clock
        origin seconds); returns the sessions that have finished."""
        self._merge()
        n = len(self.sessions)
        cur = np.fromiter((s.cursor for s in self.sessions), np.int64, n)
        moved = cur > self.last
        if record:
            self.step_frames.append(int((cur - self.last).sum()))
            self.step_streams.append(int(moved.sum()))
            due = self.arrival[moved] + cur[moved] * self.frame_s
            if due.size and not np.isnan(due[0]):
                self.latencies.append(t_end - due)
                self.early += int((t_start < due - 1e-9).sum())
        done = cur >= self.length
        finished = []
        if done.any():
            finished = [s for s, d in zip(self.sessions, done) if d]
            keep = ~done
            self.sessions = [s for s, d in zip(self.sessions, done) if not d]
            self.arrival = self.arrival[keep]
            self.length = self.length[keep]
            cur = cur[keep]
        self.last = cur
        return finished

    def all_latencies(self) -> np.ndarray:
        return (np.concatenate(self.latencies) if self.latencies
                else np.zeros(0))


@dataclasses.dataclass
class Window:
    """What a loop saw in its window (clock origin seconds).  Per window
    step: ``lateness_s``, see the loops; ``pending``, the admission queue
    before the step; ``step_s`` and ``step_cpu_s``, the wall and main-thread
    CPU seconds of ``step()``."""
    open_s: float = None
    close_s: float = None
    lateness_s: list = dataclasses.field(default_factory=list)
    pending: list = dataclasses.field(default_factory=list)
    step_s: list = dataclasses.field(default_factory=list)
    step_cpu_s: list = dataclasses.field(default_factory=list)
    submitted: int = 0

    @property
    def steps(self) -> int:
        return len(self.step_s)

    @property
    def seconds(self) -> float:
        return self.close_s - self.open_s

    def step(self, engine, em: Emissions, on_finish, clock, ready: float
             ) -> float:
        """One ``engine.step()`` and its accounting; ``ready`` is when the
        step could have begun.  Returns the step's end."""
        in_window = self.open_s is not None
        t0, c0 = clock(), time.thread_time()
        if in_window:
            self.lateness_s.append(t0 - ready)
            self.pending.append(len(engine.sched.pending))
        with span('step'):
            engine.step()
        t1 = clock()
        if in_window:
            self.step_s.append(t1 - t0)
            self.step_cpu_s.append(time.thread_time() - c0)
            self.close_s = t1
        with span('account'):
            on_finish(em.after_step(t0, t1, in_window), in_window)
        return t1


def submit_ticks(schedule, chunk: int, frame_s: float) -> np.ndarray:
    """Tick at which each live stream is submitted: the first tick at or
    after the full arrival of its first chunk."""
    period = chunk * frame_s
    first = schedule.arrival_s + np.minimum(chunk, schedule.length) * frame_s
    return np.maximum(0, np.ceil(first / period - 1e-9)).astype(np.int64)


def run_live(engine, schedule, em: Emissions, *, chunk: int, frame_s: float,
             warmup_s: float, seconds: float,
             on_finish: Callable[[list, bool], None],
             on_window: Optional[Callable[[bool], None]] = None,
             clock=time.perf_counter, sleep=time.sleep) -> Window:
    """Step ``engine`` once per tick; return the window's record.

    ``on_finish(sessions, in_window)`` takes finished sessions after each
    step; ``on_window(opening)`` is called as the window opens and closes.
    ``lateness_s`` is how late each window step began after its tick, or
    after the previous step's end where that was later: the harness's own
    work between steps."""
    period = chunk * frame_s
    ticks = submit_ticks(schedule, chunk, frame_s)
    k_open = int(math.ceil(warmup_s / period - 1e-9))
    origin = clock()
    now = lambda: clock() - origin
    w, nxt, k, prev_end = Window(), 0, 0, 0.0
    while True:
        t_tick = k * period
        if k == k_open:
            w.open_s = max(t_tick, now())
            if on_window:
                on_window(True)
        if w.open_s is not None and max(t_tick, now()) >= w.open_s + seconds:
            break
        with span('tick_wait'):
            wait = t_tick - now()
            if wait > 0:
                sleep(wait)
        with span('submit'):
            while nxt < len(schedule) and ticks[nxt] <= k:
                sess = engine.submit(schedule.frames(nxt))
                em.add(sess, schedule.arrival_s[nxt], schedule.length[nxt])
                nxt += 1
        prev_end = w.step(engine, em, on_finish, now, max(t_tick, prev_end))
        k += 1
    if on_window:
        on_window(False)
    w.submitted = nxt
    return w


def run_backlog(engine, schedule, em: Emissions, *, slots: int,
                queue_slots: float, warmup_steps: int, seconds: float,
                on_finish: Callable[[list, bool], None],
                on_window: Optional[Callable[[bool], None]] = None,
                clock=time.perf_counter) -> Window:
    """Step ``engine`` back to back with at least ``queue_slots`` grids of
    streams queued; the window opens after ``warmup_steps`` steps (the
    first fills the grid).  ``lateness_s`` is the harness's own time
    between the end of one step and the start of the next."""
    depth = int(math.ceil(queue_slots * slots))
    origin = clock()
    now = lambda: clock() - origin
    w, nxt, k, prev_end = Window(), 0, 0, 0.0
    while True:
        if k == warmup_steps:
            w.open_s = now()
            if on_window:
                on_window(True)
        if w.open_s is not None and now() >= w.open_s + seconds:
            break
        with span('submit'):
            while len(engine.sched.pending) < depth:
                if nxt == len(schedule):
                    raise RuntimeError('backlog schedule exhausted: make '
                                       'more streams for this window')
                sess = engine.submit(schedule.frames(nxt))
                em.add(sess, math.nan, schedule.length[nxt])
                nxt += 1
        prev_end = w.step(engine, em, on_finish, now, prev_end)
        k += 1
    if on_window:
        on_window(False)
    w.submitted = nxt
    return w
