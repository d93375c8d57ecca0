"""Serving driver: continuous batching on the shared slot scheduler.

The serving analogue of the paper's deployment story: weights stay resident
(weight-stationary, C3), requests stream through.  Two front-ends share the
``serving.SlotScheduler`` admission/eviction/refill policy:

  * **Token families** (`SlotServer`): a fixed number of decode slots share
    one jit'd ``decode_step``; finished slots are refilled from the queue
    without stopping the others (continuous batching a la Orca/vLLM, minus
    paged KV — the ring/linear caches live in models/*).
  * **The LSTM family** (`StreamServer`): frame streams are served by the
    packed multi-stream ``serving.StreamingEngine`` (DESIGN.md §7) — all
    active utterances advance through ONE batched chunked call to the
    whole-sequence LSTM path per step, ragged tails masked, per-stream
    ``(h, c)`` state carried across chunks in the packed session cache.
    With ``--lstm-backend pallas_seq_fused`` that one call is additionally
    ONE kernel launch for the whole stack (the §8 wavefront kernel), so a
    chunk costs a single launch across all streams AND all layers.  With
    ``--systolic-topology graves-75 --lstm-backend pallas_seq_fused_systolic``
    the same call runs the paper's full 3x(5x5) Table-2 topology (§9):
    each 5x5 stage holds one layer's weights stationary and the chunk
    pipelines stage to stage via ppermute.

Serves the named architecture at its full width by default (the paper's
123 -> 3x421 -> 62 CTC model for ``chipmunk-ctc``); ``--smoke`` swaps in the
reduced same-family config that CPU tests use:
  python -m repro.launch.serve --arch chipmunk-ctc --requests 6
  python -m repro.launch.serve --arch qwen3-14b --smoke --requests 6
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..compile_cache import enable_compile_cache
from ..models import get_bundle
from ..serving import SlotScheduler, StreamingEngine


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    out: Optional[List[int]] = None
    t_enqueue: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # prompt tokens not yet prefetched into the slot's cache — a declared
    # field (reset on admission), not an attribute patched on from outside
    _prefill_left: List[int] = dataclasses.field(default_factory=list)


class SlotServer:
    """num_slots concurrent decodes; greedy sampling; per-slot refill.

    For simplicity each slot owns an independent cache (batch dim 1) — slot
    refill never perturbs neighbours.  Prefill reuses the decode path (token
    by token) for the smoke scale; the 32k-prefill path is exercised by the
    dry-run's ``forward`` lowering.  Slot bookkeeping lives in the shared
    ``serving.SlotScheduler``; this class owns only the caches.
    """

    def __init__(self, cfg, params, num_slots=4, max_seq=128):
        self.cfg = cfg
        self.bundle = get_bundle(cfg)
        self.params = params
        self.max_seq = max_seq
        self.sched: SlotScheduler[Request] = SlotScheduler(num_slots)
        self.caches = [self.bundle.init_cache(1, max_seq)[0]
                       for _ in range(num_slots)]
        self.pos = [0] * num_slots
        self._step = jax.jit(self.bundle.decode_step)

    @property
    def done(self) -> List[Request]:
        return self.sched.done

    def submit(self, req: Request):
        req.t_enqueue = time.time()
        req.out = []
        self.sched.submit(req)

    def _admit(self, i: int, req: Request):
        # fresh cache per admission: a recycled slot never leaks state
        self.caches[i] = self.bundle.init_cache(1, self.max_seq)[0]
        self.pos[i] = 0
        req._prefill_left = list(req.prompt)

    def step(self):
        """One decode step across all active slots."""
        self.sched.refill(self._admit)
        for i, req in self.sched.active():
            if req._prefill_left:
                tok = req._prefill_left.pop(0)
                emit = not req._prefill_left
            else:
                tok = req.out[-1]
                emit = True
            logits, self.caches[i] = self._step(
                self.params, self.caches[i],
                jnp.asarray([[tok]], jnp.int32), jnp.int32(self.pos[i]))
            self.pos[i] += 1
            if emit:
                nxt = int(jnp.argmax(logits[0, -1]))
                if req.t_first is None:
                    req.t_first = time.time()
                req.out.append(nxt)
                if len(req.out) >= req.max_new or self.pos[i] >= self.max_seq - 1:
                    req.t_done = time.time()
                    self.sched.finish(i)

    def drain(self):
        while self.sched.busy:
            self.step()


class StreamServer:
    """Frame-stream serving for the LSTM family on the packed engine.

    Thin front-end over ``serving.StreamingEngine``: utterances in, per-frame
    CTC log-probs (and incrementally decoded phonemes) out.  Unlike the
    token path there is no per-slot jit call — every engine step advances
    ALL active streams through one batched chunked whole-sequence call, so
    the resident weights are fetched once per chunk for the entire slot grid.
    """

    def __init__(self, cfg, params, num_slots=4, chunk=16, faults=None,
                 async_dispatch=False, deadline_slo=None):
        policy = None
        if deadline_slo is not None:
            from ..runtime import ChunkSizePolicy
            from ..serving.engine import tuned_chunk_ceiling
            # a tuned staged chunk depth (schedule cache, repro.tune) caps
            # how deep the policy may grow chunks; scheduling-only (§11)
            ceiling = tuned_chunk_ceiling(cfg, chunk, num_slots)
            policy = ChunkSizePolicy(chunk_max=ceiling, slack=deadline_slo)
        self.engine = StreamingEngine(cfg, params, max_streams=num_slots,
                                      chunk=chunk, decode_ctc=True,
                                      faults=faults,
                                      async_dispatch=async_dispatch,
                                      chunk_policy=policy)

    def submit(self, frames: np.ndarray, priority: int = 0):
        return self.engine.submit(frames, priority=priority)

    def drain(self):
        return self.engine.run()

    @property
    def done(self):
        return self.engine.sched.done


def _run_token_serving(cfg, args):
    bundle = get_bundle(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    server = SlotServer(cfg, params, num_slots=args.slots)

    rng = np.random.RandomState(0)
    t0 = time.time()
    for r in range(args.requests):
        prompt = rng.randint(1, cfg.vocab_size, size=rng.randint(3, 8)).tolist()
        server.submit(Request(rid=r, prompt=prompt, max_new=args.max_new))
    server.drain()
    wall = time.time() - t0
    toks = sum(len(r.out) for r in server.done)
    lat = [r.t_done - r.t_enqueue for r in server.done]
    print(f'served {len(server.done)} requests, {toks} tokens in {wall:.2f}s '
          f'({toks / wall:.1f} tok/s); p50 latency {np.median(lat):.2f}s')
    for r in sorted(server.done, key=lambda r: r.rid)[:3]:
        print(f'  req {r.rid}: prompt {r.prompt[:4]}... -> {r.out}')


def _parse_at_spec(spec: str):
    """Parse a repeatable ``VALUE@STEP`` injection flag into (step, value)."""
    value, step = spec.split('@')
    return int(step), int(value)


def _build_fault_config(args):
    """Assemble a ``runtime.ServingFaultConfig`` from the CLI fault flags;
    returns None when no fault feature was requested (the engine then runs
    the zero-overhead non-fault path)."""
    from ..runtime import ServingFaultConfig
    fail_at = dict(_parse_at_spec(s) for s in (args.fail_engines or []))
    if args.fail_at_step is not None and args.fail_at_step not in fail_at:
        fail_at[args.fail_at_step] = 1
    poison_at = dict(_parse_at_spec(s) for s in (args.poison_slot or []))
    recover_at = dict(_parse_at_spec(s) for s in (args.recover_at or []))
    if not (fail_at or poison_at or recover_at or args.stream_ckpt_dir
            or args.deadline_factor is not None):
        return None
    return ServingFaultConfig(fail_at=fail_at, poison_at=poison_at,
                              recover_at=recover_at,
                              promote_hysteresis=args.promote_hysteresis,
                              canary=args.canary,
                              backoff_s=0.0,
                              deadline_factor=args.deadline_factor,
                              checkpoint_dir=args.stream_ckpt_dir)


def _run_stream_serving(cfg, args):
    bundle = get_bundle(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    faults = _build_fault_config(args)
    server = StreamServer(cfg, params, num_slots=args.slots, chunk=args.chunk,
                          faults=faults, async_dispatch=args.async_dispatch,
                          deadline_slo=args.deadline_slo)

    rng = np.random.RandomState(0)
    t0 = time.time()
    for r in range(args.requests):
        frames = rng.randn(rng.randint(args.chunk, 4 * args.chunk),
                           cfg.lstm_inputs).astype(np.float32) * 0.5
        # every 3rd utterance is a latency-SLO stream (§11 priority demo)
        server.submit(frames, priority=1 if r % 3 == 2 else 0)
    server.drain()
    wall = time.time() - t0
    stats = server.engine.stats()
    mode = 'async' if stats['async'] else 'sync'
    print(f'streamed {stats["streams"]} utterances, {stats["frames"]} frames '
          f'in {wall:.2f}s ({stats["frames"] / wall:.1f} frames/s, {mode}); '
          f'p50 latency {stats["p50_latency_s"]:.3f}s, '
          f'p50 chunk {stats["p50_chunk_s"] * 1e3:.1f}ms')
    if args.deadline_slo is not None:
        print(f'deadline slo: chunk_len={stats["chunk_len"]} '
              f'deadline_misses={stats["deadline_misses"]}')
    for s in sorted(server.done, key=lambda s: s.sid)[:3]:
        print(f'  stream {s.sid}: {s.length} frames -> '
              f'phonemes {s.decoder.symbols[:8]}')
    if faults is not None:
        counts = stats['event_counts']
        print(f'fault summary: backend={stats["backend"]} '
              f'rung={stats["rung"]} '
              f'degrade={counts.get("degrade", 0)} '
              f'promote={counts.get("promote", 0)} '
              f'quarantine={counts.get("quarantine", 0)} '
              f'deadline_misses={stats["deadline_misses"]} '
              f'checkpoints={counts.get("checkpoint", 0)} '
              f'events_dropped={stats["events_dropped"]}')
        for e in stats['events']:
            if e['kind'] == 'degrade':
                print(f'  degrade @step {e["step"]}: {e["from_backend"]} -> '
                      f'{e["to_backend"]} ({e["n_dead"]} engine(s) dead, '
                      f'domain {e["domain"]})')
            elif e['kind'] == 'heal':
                print(f'  heal @step {e["step"]}: domains {e["domains"]} '
                      f'healed')
            elif e['kind'] == 'promote_canary':
                print(f'  promote_canary @step {e["step"]}: replaying '
                      f'committed chunk on {e["to_backend"]}')
            elif e['kind'] == 'promote':
                print(f'  promote @step {e["step"]}: {e["from_backend"]} -> '
                      f'{e["to_backend"]} (healthy domains {e["healthy"]})')
            elif e['kind'] == 'promote_rejected':
                print(f'  promote_rejected @step {e["step"]}: '
                      f'{e["to_backend"]} canary mismatch '
                      f'(backoff -> {e["backoff"]})')


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='chipmunk-ctc')
    ap.add_argument('--smoke', action='store_true',
                    help='use the reduced config (CPU-runnable)')
    ap.add_argument('--requests', type=int, default=6)
    ap.add_argument('--slots', type=int, default=3)
    ap.add_argument('--max-new', type=int, default=8)
    ap.add_argument('--chunk', type=int, default=8,
                    help='frames per engine step (LSTM streaming only)')
    from ..core.lstm import BACKENDS
    from .mesh import SYSTOLIC_TOPOLOGIES
    ap.add_argument('--lstm-backend', default='auto', choices=BACKENDS,
                    help='LSTM execution engine (recurrent families)')
    ap.add_argument('--systolic-topology', default=None,
                    choices=sorted(SYSTOLIC_TOPOLOGIES),
                    help='install a systolic mesh preset before serving '
                         '(stage-1 presets enable/auto-select '
                         'pallas_seq_systolic, stage>1 presets the staged '
                         'pallas_seq_fused_systolic; multi-device presets '
                         'need that many JAX devices)')
    from .mesh import DIE_TOPOLOGIES
    ap.add_argument('--die-topology', default=None,
                    choices=sorted(DIE_TOPOLOGIES),
                    help='install a two-level die-mesh preset (§14): dies '
                         'are fault domains; an engine failure re-forms '
                         'the systolic mesh on the surviving dies (an '
                         'intermediate ladder rung) and a healed die is '
                         'canary-validated back in; needs dies*stage*rows*'
                         'cols JAX devices')
    ap.add_argument('--fail-at-step', type=int, default=None,
                    help='declare one mesh engine dead at this engine step '
                         '(LSTM streaming; exercises the degradation ladder)')
    ap.add_argument('--fail-engines', action='append', default=None,
                    metavar='N@STEP',
                    help='declare N engines dead at STEP (repeatable)')
    ap.add_argument('--poison-slot', action='append', default=None,
                    metavar='SLOT@STEP',
                    help='poison slot SLOT with NaN state before STEP '
                         '(repeatable; exercises quarantine)')
    ap.add_argument('--recover-at-step', dest='recover_at', action='append',
                    default=None, metavar='N@STEP',
                    help='heal N failed fault domains at engine step STEP '
                         '(repeatable; exercises the §14 canary-validated '
                         'climb back up the ladder)')
    ap.add_argument('--promote-hysteresis', type=int, default=4,
                    help='engine steps a promotion must wait after a '
                         'failure/promotion/rejection; flaps and rejected '
                         'canaries double it (exponential backoff)')
    ap.add_argument('--no-canary', dest='canary', action='store_false',
                    default=True,
                    help='promote on capacity + hysteresis alone, without '
                         'the shadow-replay canary validation')
    ap.add_argument('--stream-ckpt-dir', default=None,
                    help='directory for per-stream (h, c) + cursor '
                         'checkpoints (enables preempt/resume across runs)')
    ap.add_argument('--deadline-factor', type=float, default=None,
                    help='per-chunk deadline as a multiple of the paper '
                         'real-time frame budget (records deadline_miss '
                         'events)')
    ap.add_argument('--async', dest='async_dispatch', action='store_true',
                    help='double-buffered dispatch (DESIGN.md §11): the '
                         'next chunk is packed and launched while the '
                         'in-flight one computes; outputs stay bit-equal '
                         'to sync serving')
    ap.add_argument('--deadline-slo', type=float, default=None,
                    metavar='FACTOR',
                    help='attach the deadline-aware chunk-size policy: '
                         'budget = chunk * 10ms frame period * FACTOR '
                         '(the Table-2 arrival rate); chunk length adapts '
                         'to observed launch-to-commit wall times')
    ap.add_argument('--schedule-cache', default=None, metavar='PATH',
                    help='install a measured-schedule cache (repro.tune '
                         'JSON): dispatch decisions — int8 fused-vs-'
                         'layerwise, stack backend, staged Tc, the chunk-'
                         'policy ceiling — consult its winners before any '
                         'heuristic; dispatch-only, numerics unchanged')
    ap.add_argument('--tune', action='store_true',
                    help='run the offline autotuner for this serving '
                         'config before serving (LSTM family only): '
                         'measured int8 backend trial + the measured '
                         'end-to-end serving-loop chunk ceiling (the real '
                         'engine step, outputs bit-equal across depths by '
                         'the §7 contract) with the kernel-level predicted '
                         'ceiling as fallback, recorded to --schedule-cache '
                         'when given; serving itself never pays tuning cost')
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.systolic_topology:
        from .mesh import install_systolic_topology
        mesh = install_systolic_topology(args.systolic_topology)
        print(f'installed systolic topology {args.systolic_topology}: '
              f'{dict(mesh.shape)}')
    if args.die_topology:
        from .mesh import install_die_topology
        dm = install_die_topology(args.die_topology)
        print(f'installed die topology {args.die_topology}: {dm.dies} dies '
              f'x {dm.engines_per_die} engines '
              f'({dm.dies}x{dm.stage}x{dm.rows}x{dm.cols})')

    if args.schedule_cache:
        import pathlib
        from ..tune import ScheduleCache, install_schedule_cache
        path = pathlib.Path(args.schedule_cache)
        cache = (ScheduleCache.load(path) if path.exists()
                 else ScheduleCache())
        install_schedule_cache(cache)
        print(f'installed schedule cache: {len(cache)} entries '
              f'from {path}' if path.exists()
              else f'installed empty schedule cache (will tune into {path})')

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch)).replace(
               lstm_backend=args.lstm_backend)
    if args.tune and cfg.family == 'lstm':
        from ..tune import (ScheduleCache, current_schedule_cache,
                            install_schedule_cache, tune_serving_config)
        cache = current_schedule_cache()
        if cache is None:
            cache = install_schedule_cache(ScheduleCache())
        entries = tune_serving_config(cfg, chunk=args.chunk,
                                      slots=args.slots, cache=cache)
        for e in entries:
            what = e.backend or f'Tc={e.tc}'
            print(f'tuned {e.kind}: {what} ({e.source})')
        if args.schedule_cache:
            cache.save(args.schedule_cache)
            print(f'saved {len(cache)} entries -> {args.schedule_cache}')
    if cfg.family == 'lstm':
        _run_stream_serving(cfg, args)
    else:
        _run_token_serving(cfg, args)


if __name__ == '__main__':
    main()
