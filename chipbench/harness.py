"""One run of one cell: set-up and warm-up, the measured window, the check
of what the window produced, and the metrics.

The system under test is the repository's streaming front end
(``launch.serve.StreamServer`` over ``serving.StreamingEngine``), driven
through ``submit`` and ``engine.step()`` only.  The benchmark makes the
weights and the frames from the seed, and keeps its own reference; the
configuration's model kind (``models/<kind>.py``) makes the weights and
hands them to the program.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import Optional

import jax
import numpy as np

from . import check, loop, trace as trace_mod, traffic
from .stalls import StallWatch
from .bench import Spec

COMPILE_EVENTS = ('/jax/core/compile/backend_compile_duration',
                  '/jax/core/compile/jaxpr_trace_duration')


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a metric's reader sees."""
    setup_s: float
    window: loop.Window
    latencies_s: np.ndarray        # every chunk emission in the window
    step_frames: np.ndarray        # frames committed by each window step
    step_streams: np.ndarray       # streams that emitted in each window step
    model: dict
    kind: object                   # the model kind's module
    serving: dict
    peaks: dict
    trace: Optional[trace_mod.Trace] = None


class CompileCounter:
    """Counts traces and compiles between ``arm`` and ``disarm``."""

    def __init__(self):
        self.count = 0

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1

    def arm(self) -> None:
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def disarm(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcPauses:
    """The longest pause of Python's garbage collector between ``arm`` and
    ``disarm``."""

    def __init__(self):
        self.longest = 0.0
        self._start = 0.0

    def _on(self, phase: str, _info: dict) -> None:
        if phase == 'start':
            self._start = time.perf_counter()
        else:
            self.longest = max(self.longest,
                               time.perf_counter() - self._start)

    def arm(self) -> None:
        gc.callbacks.append(self._on)

    def disarm(self) -> None:
        gc.callbacks.remove(self._on)


def schedule_for(mix: dict, config: dict, seed: int, seconds: float):
    m, s = config['model'], config['serving']
    frame_s = s['frame_ms'] / 1000
    if mix['kind'] == 'live':
        return traffic.live(mix, seed=seed, n_features=m['inputs'],
                            horizon_s=mix['warmup_s'] + seconds + 5,
                            frame_s=frame_s)
    # enough streams to keep the queue deep however fast the engine runs
    per_s = mix['max_frames_per_s'] / (mix['length_s']['min'] / frame_s)
    n = int(s['slots'] * (1 + mix['queue_slots']) + per_s * seconds) + 1
    return traffic.backlog(mix, seed=seed, n_features=m['inputs'],
                           slots=s['slots'], n_streams=n, frame_s=frame_s)


def enable_cache() -> None:
    """JAX's persistent compilation cache, every program in it."""
    from repro.compile_cache import enable_compile_cache
    log(f'compile cache: {enable_compile_cache()}')
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)


def start_server(config: dict, kind, weights: dict):
    """The program's streaming front end over the benchmark's weights, as
    the model kind ``kind`` hands them to it."""
    from repro.launch.serve import StreamServer
    serving = config['serving']
    arch, params = kind.program(config, weights)
    server = StreamServer(arch, params, num_slots=serving['slots'],
                          chunk=serving['chunk'])
    engine = server.engine
    if (engine.decode_ctc, engine.async_dispatch) != (
            serving['decode_ctc'], serving['async_dispatch']):
        raise ValueError('the configuration states a decode or dispatch mode '
                         'that StreamServer does not run')
    log(f'resolved backend: {engine.backend} ({serving["slots"]} slots, '
        f'chunk {serving["chunk"]})')
    return server


def drive(engine, mix: dict, config: dict, sched, em: loop.Emissions,
          **kw) -> loop.Window:
    """The mix's loop over ``engine``: warm-up, then the window."""
    serving = config['serving']
    if mix['kind'] == 'live':
        return loop.run_live(engine, sched, em, chunk=serving['chunk'],
                             frame_s=serving['frame_ms'] / 1000,
                             warmup_s=mix['warmup_s'], **kw)
    return loop.run_backlog(engine, sched, em, slots=serving['slots'],
                            queue_slots=mix['queue_slots'],
                            warmup_steps=mix['warmup_steps'], **kw)


def run_cell(spec: Spec, cell: dict, *, seed: int, seconds: float,
             traced: bool, t_start: float, peaks: dict) -> dict:
    """Everything after the look for a chip; returns the result line."""
    config, mix = spec.config(cell), spec.traffic(cell)
    model, serving = config['model'], config['serving']
    frame_s = serving['frame_ms'] / 1000
    enable_cache()
    steps = [0]
    counter, gc_pauses = CompileCounter(), GcPauses()
    watch = StallWatch(progress=lambda: steps[0])

    kind = spec.model(config)
    weights = jax.block_until_ready(kind.make_weights(model, seed))
    server = start_server(config, kind, weights)
    engine = server.engine
    sched = schedule_for(mix, config, seed, seconds)
    em = loop.Emissions(frame_s)
    sample = check.Sample(mix['sample_streams'],
                          np.random.default_rng([seed, 3]))
    state = {'setup_s': None, 'trace_dir': None, 'span': None}

    def on_finish(sessions, in_window):
        steps[0] += 1
        engine.sched.done.clear()      # results handed to their clients
        if in_window:
            sample.offer(sessions)

    def on_window(opening):
        if opening:
            state['setup_s'] = time.perf_counter() - t_start
            if traced:
                state['trace_dir'] = tempfile.mkdtemp(
                    prefix='chipbench-trace-')
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(state['trace_dir'],
                                         profiler_options=opts)
                state['span'] = loop.span('window')
                state['span'].__enter__()
            counter.arm()
            gc_pauses.arm()
            watch.start()
        else:
            counter.disarm()
            gc_pauses.disarm()
            watch.stop()
            if traced:
                state['span'].__exit__(None, None, None)
                jax.profiler.stop_trace()

    window = drive(engine, mix, config, sched, em, seconds=seconds,
                   on_finish=on_finish, on_window=on_window)
    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = stats.get('peak_bytes_in_use', 0)

    lat = em.all_latencies()
    step_frames = np.array(em.step_frames)
    step_streams = np.array(em.step_streams)
    log(f'window: {window.seconds:.3f} s, {window.steps} steps, '
        f'{int(step_frames.sum())} frames, {len(lat)} emissions, '
        f'{window.submitted} streams submitted, {len(em.sessions)} in '
        f'flight at the close')
    log(f'traces and compiles inside the window: {counter.count}')
    q = np.percentile(np.array(window.lateness_s) * 1e3, [50, 99, 100])
    log(f'generator lateness ms: p50 {q[0]:.3f} p99 {q[1]:.3f} '
        f'max {q[2]:.3f}')
    slow = int(np.argmax(window.step_s))
    log(f'slowest step: {window.step_s[slow] * 1e3:.3f} ms wall, '
        f'{window.step_cpu_s[slow] * 1e3:.3f} ms main-thread CPU; '
        f'longest pause for garbage collection: {gc_pauses.longest * 1e3:.3f}'
        f' ms')
    for secs, cpu, where in watch.longest():
        log(f'main thread blocked {secs:.3f} s ({cpu:.3f} s on the CPU) '
            f'at: {" <- ".join(where)}')
    log(f'admission queue: first {window.pending[0]} last '
        f'{window.pending[-1]} max {max(window.pending)}')
    log(f'streams emitting per step: mean {step_streams.mean():.1f}')
    log(f'chunks processed before their last frame was due: {em.early}')
    attempted = window.submitted

    # free the program's state before the reference runs on the chip
    del server, engine, em
    gc.collect()

    streams = sample.streams()
    reference = spec.reference(config['reference'])
    rows = mix['sample_streams'] + 1
    longest = int(round(mix['length_s']['max'] / frame_s))
    ref_lp = check.reference_log_probs(reference, config, weights, streams,
                                       rows, longest)
    values = check.numbers(streams, ref_lp, kind.outputs)
    correct, checks = check.judge(values, config['limits'])
    log('read, not compared: ' + ' '.join(
        f'{k} {v:.6g}' for k, v in values.items() if k not in checks))
    if not streams:
        log('no stream finished in the window: nothing came to compare')
        correct = False
    log(f'compared {len(streams)} streams, '
        f'{sum(len(s[0]) for s in streams)} frames, against the reference '
        f'with {config["products"]} products')

    tr = None
    if traced:
        tr = trace_mod.load(trace_mod.find_xplane(state['trace_dir']))
        shutil.rmtree(state['trace_dir'], ignore_errors=True)
    run = Run(setup_s=state['setup_s'], window=window, latencies_s=lat,
              step_frames=step_frames, step_streams=step_streams,
              model=model, kind=kind, serving=serving, peaks=peaks,
              trace=tr)
    metrics = {}
    for m in spec.metrics(cell, 'per_layer' if traced else 'end_to_end'):
        value = spec.reader(m['name'])(run)
        if value is not None:
            metrics[m['name']] = {'value': value, 'unit': m['unit']}
    device = {'memory_peak_bytes': int(memory_peak)}
    result = {'correct': bool(correct), 'attempted': int(attempted),
              'failed': 0 if correct else len(streams), 'metrics': metrics,
              'device': device}
    if tr is not None:
        lo, hi = tr.window()
        device['busy_s'] = tr.busy_s()
        device['window_s'] = hi - lo
        result['breakdown'] = {'device_ops': tr.top_ops(),
                               'idle_gaps': tr.idle_gaps()}
    for name, c in checks.items():
        log(f'check {name}: {c["value"]:.6g} (limit {c["limit"]:g})')
    result['checks'] = checks
    return result
