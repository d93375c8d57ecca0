"""Named host spans of the serving engine on the profiler's trace.

Each phase of ``StreamingEngine.step`` (admission, packing, dispatch, the
wait on the device, the device->host copy, delivery) and each ``submit``
runs inside a ``serving.<phase>`` ``jax.profiler.TraceAnnotation``.  While
a trace is taken (``jax.profiler.start_trace``) the spans land on the
profiler's host plane, on the clock the profiler gives the device planes,
and their integer counters become the events' stats; otherwise a span
formats nothing and costs under a microsecond of host time (0.6-0.9 us on
a TPU v5e host).  A counter known only after the work is set on the open
span with ``set_metadata``.  ``serving_events`` reads the spans back from
a saved trace.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

import jax

PREFIX = 'serving.'

Event = Tuple[float, float, Dict[str, int]]


def span(name: str, **counters: int) -> jax.profiler.TraceAnnotation:
    """The ``serving.<name>`` span, with ``counters`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counters)


def serving_events(path: str) -> Dict[str, List[Event]]:
    """The ``serving.*`` host events of a trace, by span name without the
    prefix: ``[(start_s, end_s, counters)]`` sorted by start, in seconds
    on the trace's clock.  ``path`` is an ``.xplane.pb`` file or a
    directory holding one (``jax.profiler.start_trace``'s log dir)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path, = glob.glob(f'{path}/**/*.xplane.pb', recursive=True)
    out: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith('/host:'):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.setdefault(ev.name[len(PREFIX):], []).append(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         {k: v for k, v in ev.stats}))
    return {k: sorted(v, key=lambda e: e[0]) for k, v in out.items()}
