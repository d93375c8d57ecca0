"""Where the main thread sat when it blocked.

A daemon thread samples the main thread's innermost frames every
``period_s``, with a progress count the caller advances (one per engine
step) and the main thread's CPU clock.  A run of samples with the same
frames and the same count that lasts ``min_s`` or longer, in which the main
thread used under half of the wall time on the CPU, is a stall; the
harness prints the longest ones, so a gap in the window can be pinned to
the call that blocked.  A sample reads only the frames' code locations;
the stack is formatted once, for a stall.
"""
from __future__ import annotations

import sys
import threading
import time


class StallWatch:

    def __init__(self, progress, period_s: float = 0.025, min_s: float = 0.1,
                 depth: int = 6):
        self.progress = progress
        self.period_s, self.min_s, self.depth = period_s, min_s, depth
        self.stalls: list = []          # (seconds, CPU seconds, where)
        self._main = threading.main_thread().ident
        self._cpu = time.pthread_getcpuclockid(self._main)
        self._stop = threading.Event()
        self._thread = None

    def _sample(self) -> tuple:
        frame = sys._current_frames().get(self._main)
        where = []
        while frame is not None and len(where) < self.depth:
            code = frame.f_code
            where.append(f'{code.co_filename}:{frame.f_lineno} '
                         f'{code.co_name}')
            frame = frame.f_back
        return self.progress(), tuple(where)

    def _watch(self) -> None:
        last, since, cpu0 = None, time.perf_counter(), 0.0
        while True:
            stopping = self._stop.wait(self.period_s)
            sample, now = self._sample(), time.perf_counter()
            cpu = time.clock_gettime(self._cpu)
            if sample != last or stopping:
                wall = now - since
                if last and wall >= self.min_s and cpu - cpu0 < wall / 2:
                    self.stalls.append((wall, cpu - cpu0, last[1]))
                last, since, cpu0 = sample, now, cpu
            if stopping:
                return

    def start(self) -> None:
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def longest(self, n: int = 3) -> list:
        return sorted(self.stalls, key=lambda s: -s[0])[:n]
