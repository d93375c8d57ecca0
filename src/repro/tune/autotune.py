"""Measured-schedule autotuner: timed trials over the pruned shmoo space.

``shmoo`` enumerates + ranks candidates by the calibrated model; this module
graduates the top of each ranking to INTERLEAVED timed trials (candidate
A/B/A/B per iteration, the ``benchmarks/`` discipline — back-to-back medians
are biased by whichever candidate runs during a busy host window) and
records the winner in a ``schedule.ScheduleCache``.  Tuning is strictly
offline: serving and CI consult the persisted cache and never pay trial
cost at request time (``replay_check`` pins that the recorded predicted
winners are reproducible from the recorded space without running anything).

Every candidate a trial compares is numerics-equivalent by construction
(the §7/§9 contracts: chunk depth and in-stage order are schedule-only;
int8 fused vs layerwise is bit-identical), and ``tune_staged_stack``
re-asserts bitwise equality across its candidates before timing them — an
autotuner must never be able to trade correctness for speed.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from .schedule import (ANY_MESH, ScheduleCache, ScheduleEntry,
                       devices_signature, host_fingerprint, mesh_signature)
from .shmoo import (GeometryCandidate, ShmooRecord, StagedCandidate, TC_GRID,
                    enumerate_geometry_candidates, enumerate_lb_candidates,
                    enumerate_staged_candidates, predict_geometry_us,
                    predict_staged_us, rank_geometry_candidates,
                    rank_lb_candidates, rank_staged_candidates)


def measure_interleaved(fns: Sequence[Callable[[], object]], *,
                        iters: int = 3, warmup: int = 1) -> List[float]:
    """Median wall-clock us for each thunk, interleaved A/B/C per iteration
    so host-load drift hits every candidate equally."""
    import jax
    for fn in fns:
        for _ in range(warmup):
            jax.block_until_ready(fn())
    walls: List[List[float]] = [[] for _ in fns]
    for _ in range(iters):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            walls[i].append(time.perf_counter() - t0)
    return [sorted(w)[len(w) // 2] * 1e6 for w in walls]


# ---------------------------------------------------------------------------
# Staged scale-out schedule (Tc, in-stage order) — needs the mesh's devices
# ---------------------------------------------------------------------------

def tune_staged_stack(stack, mesh, xs, *, cache: Optional[ScheduleCache]
                      = None, kind: str = 'stack_f32', top_k: int = 3,
                      iters: int = 3, warmup: int = 1, measure: bool = True
                      ) -> Tuple[ScheduleEntry, List[ShmooRecord]]:
    """Tune the staged backend's ``(Tc, in_stage)`` for one placement.

    ``stack``: ``LSTMStackParams``; ``mesh``: a live (stage, row, col)
    mesh; ``xs``: (T, B, n_x) representative input.  Enumerates the
    admissible grid, ranks by ``perf_model``, and (when ``measure``) times
    the ``top_k`` predicted-best candidates interleaved through the real
    ``systolic_lstm_stack_seq`` — after asserting their outputs bitwise
    equal, so a trial can only ever pick among proven-identical schedules.
    Records and returns the winner (``source='measured'`` or
    ``'predicted'``).
    """
    import jax
    from ..core import systolic
    T, B, n_x = xs.shape
    n_h = stack.layers[0].n_h
    L = len(stack.layers)
    S = mesh.shape['stage']
    rows, cols = mesh.shape['row'], mesh.shape['col']
    assert systolic.seq_scaleout_admissible(n_h, mesh, n_layers=L), (
        'placement not admissible for the staged scale-out', mesh.shape)
    cands = enumerate_staged_candidates(n_x, n_h, L, T, B, stages=S,
                                        rows=rows, cols=cols)
    assert cands, 'no admissible staged candidate for this placement'
    ranked = rank_staged_candidates(cands, n_x, n_h, L, T)
    records = [ShmooRecord(
        suite='staged_schedule',
        params={'n_x': n_x, 'n_h': n_h, 'n_layers': L, 'T': T, 'B': B,
                'stages': c.stages, 'rows': c.rows, 'cols': c.cols,
                'bn': c.bn, 'bk': c.bk, 'lb': c.lb, 'tc': c.tc,
                'in_stage': c.in_stage},
        metrics={'predicted_us': us, 'measured_us': 0.0})
        for c, us in ranked]

    if measure:
        # top of the predicted ranking, PLUS each in-stage mode's best: the
        # model charges concurrent slots for the batched order, which a
        # single-core emulation host cannot honour — the structural
        # dichotomy must always reach the timed trial, predictions only
        # order within it.
        trial = list(ranked[:top_k])
        for mode in systolic.IN_STAGE_MODES:
            best = next(((c, u) for c, u in ranked if c.in_stage == mode),
                        None)
            if best is not None and best not in trial:
                trial.append(best)
        fns = [jax.jit(lambda x, tc=c.tc, mode=c.in_stage:
                       systolic.systolic_lstm_stack_seq(
                           stack, mesh, x, chunk=tc, in_stage=mode)[0])
               for c, _ in trial]
        outs = [np.asarray(jax.block_until_ready(f(xs))) for f in fns]
        for o in outs[1:]:     # schedule-only: every candidate bit-equal
            np.testing.assert_array_equal(o, outs[0])
        meds = measure_interleaved([lambda f=f: f(xs) for f in fns],
                                   iters=iters, warmup=warmup)
        for (c, _), us in zip(trial, meds):
            for r in records:
                if r.params['tc'] == c.tc and r.params['in_stage'] == c.in_stage:
                    r.metrics['measured_us'] = us
        win_i = int(np.argmin(meds))
        winner, pred_us = trial[win_i]
        entry = ScheduleEntry(kind=kind, n_x=n_x, n_h=n_h, n_layers=L, T=T,
                              B=B, mesh=mesh_signature(mesh), tc=winner.tc,
                              in_stage=winner.in_stage, bn=winner.bn,
                              bk=winner.bk, lb=winner.lb,
                              predicted_us=pred_us,
                              measured_us=meds[win_i], source='measured',
                              host=host_fingerprint())
    else:
        winner, pred_us = ranked[0]
        entry = ScheduleEntry(kind=kind, n_x=n_x, n_h=n_h, n_layers=L, T=T,
                              B=B, mesh=mesh_signature(mesh), tc=winner.tc,
                              in_stage=winner.in_stage, bn=winner.bn,
                              bk=winner.bk, lb=winner.lb,
                              predicted_us=pred_us, source='predicted')
    if cache is not None:
        cache.record(entry)
    return entry, records


# ---------------------------------------------------------------------------
# Geometry (mesh shape + stage split + schedule) — needs the device budget
# ---------------------------------------------------------------------------

def tune_geometry(stack, xs, *, devices: int,
                  ref: Tuple[int, int, int],
                  cache: Optional[ScheduleCache] = None, top_k: int = 3,
                  iters: int = 3, warmup: int = 1, measure: bool = True,
                  allow_reassoc: bool = False
                  ) -> Tuple[ScheduleEntry, List[ShmooRecord], float]:
    """Tune the MESH GEOMETRY itself for a device budget (DESIGN.md §13).

    ``ref`` is the balanced-default placement dispatch would build today
    (e.g. the graves-75 preset's ``(2, 5, 5)``) — it anchors both the
    speedup baseline and the bit-equality class: by default only
    candidates in the reference's arithmetic class ``(n_h_p, bk)`` are
    trialed, and their outputs are asserted BITWISE equal to the
    reference's before any timing (geometry inside a class is
    schedule-only).  ``allow_reassoc=True`` additionally trials the
    predicted-best candidates from OTHER classes, gated by an allclose
    check (a different column split re-associates the hidden contraction —
    float-equal, not bit-equal; the cache entry records which class won).

    Returns ``(winner entry, shmoo records, baseline_us)`` where
    ``baseline_us`` is the measured reference time (0.0 in predicted-only
    mode) — the honest denominator for the BENCH speedup row.
    """
    import jax
    from ..core import systolic
    T, B, n_x = xs.shape
    n_h = stack.layers[0].n_h
    L = len(stack.layers)
    cands = enumerate_geometry_candidates(n_x, n_h, L, T, B, devices=devices)
    assert cands, 'no admissible geometry for this device budget'
    ranked = rank_geometry_candidates(cands, n_x, n_h, L, T)
    records = [ShmooRecord(
        suite='geometry',
        params={'n_x': n_x, 'n_h': n_h, 'n_layers': L, 'T': T, 'B': B,
                'devices': devices, 'stages': c.stages, 'rows': c.rows,
                'cols': c.cols, 'blocks': c.blocks_str().replace(',', '+'),
                'bn': c.bn, 'bk': c.bk, 'lb': c.lb, 'tc': c.tc,
                'in_stage': c.in_stage},
        metrics={'predicted_us': us, 'measured_us': 0.0})
        for c, us in ranked]

    def _entry(cand, pred_us, meas_us, source, mesh_sig, kind='geometry'):
        return ScheduleEntry(
            kind=kind, n_x=n_x, n_h=n_h, n_layers=L, T=T, B=B,
            mesh=mesh_sig, tc=cand.tc, in_stage=cand.in_stage,
            bn=cand.bn, bk=cand.bk, lb=cand.lb, stages=cand.stages,
            rows=cand.rows, cols=cand.cols, blocks=cand.blocks_str(),
            predicted_us=pred_us, measured_us=meas_us, source=source,
            host=host_fingerprint() if source == 'measured' else '')

    if not measure:
        winner, pred = ranked[0]
        entry = _entry(winner, pred, 0.0, 'predicted',
                       devices_signature(devices))
        if cache is not None:
            cache.record(entry)
        return entry, records, 0.0

    # The reference: balanced split on the ref mesh under dispatch's
    # cold-cache defaults (chunk = ceil(T/4S), sequential in-stage order).
    rs, rr, rc = ref
    assert rs * rr * rc <= devices, ('reference exceeds the budget', ref)
    ref_splits = [c.blocks for c, _ in ranked
                  if (c.stages, c.rows, c.cols) == (rs, rr, rc)]
    assert ref_splits, 'reference placement is not admissible'
    base, rem = divmod(L, rs)
    balanced = tuple(base + (1 if s < rem else 0) for s in range(rs))
    import math as _math
    blk = _math.lcm(rr, rc)
    n_h_p = -(-n_h // blk) * blk
    ref_cand = GeometryCandidate(
        stages=rs, rows=rr, cols=rc, blocks=balanced,
        tc=max(1, -(-T // (4 * rs))), in_stage='sequential',
        bn=n_h_p // rr, bk=n_h_p // rc, n_h_p=n_h_p)
    ref_sig = ref_cand.arith_signature

    # Trial set: the reference, the predicted top_k of its arithmetic
    # class, each in-stage mode's class-best (the structural dichotomy
    # must reach the trial — see tune_staged_stack), and, only with
    # allow_reassoc, the overall predicted top_k from other classes.
    same = [(c, u) for c, u in ranked if c.arith_signature == ref_sig]
    trial: List[Tuple[GeometryCandidate, float]] = [(ref_cand, 0.0)]
    for c, u in same[:top_k]:
        if c != ref_cand:
            trial.append((c, u))
    for mode in systolic.IN_STAGE_MODES:
        best = next(((c, u) for c, u in same if c.in_stage == mode), None)
        if best is not None and best[0] != ref_cand and best not in trial:
            trial.append(best)
    n_exact = len(trial)
    if allow_reassoc:
        for c, u in ranked[:top_k]:
            if c.arith_signature != ref_sig and (c, u) not in trial:
                trial.append((c, u))

    fns = []
    for c, _ in trial:
        mesh = systolic.make_systolic_mesh(c.rows, c.cols, stage=c.stages)
        fns.append(jax.jit(
            lambda x, m=mesh, tc=c.tc, mode=c.in_stage, blks=c.blocks:
            systolic.systolic_lstm_stack_seq(stack, m, x, chunk=tc,
                                             in_stage=mode,
                                             blocks=blks)[0]))
    outs = [np.asarray(jax.block_until_ready(f(xs))) for f in fns]
    for o in outs[1:n_exact]:       # same class: bit-equal, asserted
        np.testing.assert_array_equal(o, outs[0])
    for o in outs[n_exact:]:        # other classes: re-associated, allclose
        np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-6)
    meds = measure_interleaved([lambda f=f: f(xs) for f in fns],
                               iters=iters, warmup=warmup)
    for (c, _), us in zip(trial, meds):
        key = (c.stages, c.rows, c.cols, c.blocks_str().replace(',', '+'),
               c.tc, c.in_stage)
        for r in records:
            if (r.params['stages'], r.params['rows'], r.params['cols'],
                    r.params['blocks'], r.params['tc'],
                    r.params['in_stage']) == key:
                r.metrics['measured_us'] = us
    baseline_us = meds[0]
    win_i = int(np.argmin(meds))
    winner, pred = trial[win_i]
    entry = _entry(winner, pred, meds[win_i], 'measured',
                   devices_signature(devices))
    if cache is not None:
        cache.record(entry)
        # Also land the winner's SCHEDULE under its concrete mesh key so
        # resolve_staged_chunk / resolve_staged_in_stage /
        # resolve_staged_blocks consult it whenever that mesh runs.
        win_mesh = (f'stage:{winner.stages},row:{winner.rows},'
                    f'col:{winner.cols}')
        cache.record(_entry(winner, pred, meds[win_i], 'measured',
                            win_mesh, kind='stack_f32'))
    return entry, records, baseline_us


# ---------------------------------------------------------------------------
# Single-engine §8 lb streaming factor — single device
# ---------------------------------------------------------------------------

def tune_stack_lb(n_x: int, n_h: int, n_layers: int, T: int, B: int, *,
                  cache: Optional[ScheduleCache] = None, iters: int = 3,
                  warmup: int = 1, measure: bool = True
                  ) -> Tuple[Optional[ScheduleEntry], List[ShmooRecord]]:
    """Tune the §8 fused stack's layer-block streaming factor ``lb``.

    ``lstm_stack_seq`` streams ``lb`` layers at a time through VMEM; the
    factor is grid-only (bit-equal across candidates, asserted before
    timing).  The predicted preference is the largest admissible divisor
    (fewest weight re-streams); the measured trial decides per host.
    Returns ``(entry, records)`` — entry is None when no lb is admissible
    (the backend itself is then inadmissible; nothing to record).
    """
    cands = enumerate_lb_candidates(n_x, n_h, n_layers, B)
    if not cands:
        return None, []
    ranked = rank_lb_candidates(cands, n_layers)
    records = [ShmooRecord(
        suite='stack_lb',
        params={'n_x': n_x, 'n_h': n_h, 'n_layers': n_layers, 'T': T,
                'B': B, 'lb': lb},
        metrics={'passes': passes, 'measured_us': 0.0})
        for lb, passes in ranked]
    if not measure or len(cands) == 1:
        lb = ranked[0][0]
        entry = ScheduleEntry(kind='stack_lb', n_x=n_x, n_h=n_h,
                              n_layers=n_layers, T=T, B=B, mesh=ANY_MESH,
                              lb=lb, source='predicted')
        if cache is not None:
            cache.record(entry)
        return entry, records

    import jax
    from ..core.lstm import init_lstm_stack
    from ..kernels.lstm_seq import lstm_stack_seq
    stack = init_lstm_stack(jax.random.PRNGKey(7), n_x, n_h, n_layers)
    xs = jax.random.normal(jax.random.PRNGKey(8), (T, B, n_x)) * 0.5
    fns = [jax.jit(lambda x, lb=lb: lstm_stack_seq(stack, x, lb=lb)[0])
           for lb, _ in ranked]
    outs = [np.asarray(jax.block_until_ready(f(xs))) for f in fns]
    for o in outs[1:]:              # grid-only: bit-equal by contract
        np.testing.assert_array_equal(o, outs[0])
    meds = measure_interleaved([lambda f=f: f(xs) for f in fns],
                               iters=iters, warmup=warmup)
    for (lb, _), us in zip(ranked, meds):
        for r in records:
            if r.params['lb'] == lb:
                r.metrics['measured_us'] = us
    win_i = int(np.argmin(meds))
    entry = ScheduleEntry(kind='stack_lb', n_x=n_x, n_h=n_h,
                          n_layers=n_layers, T=T, B=B, mesh=ANY_MESH,
                          lb=ranked[win_i][0], measured_us=meds[win_i],
                          source='measured', host=host_fingerprint())
    if cache is not None:
        cache.record(entry)
    return entry, records


# ---------------------------------------------------------------------------
# Int8 stack backend (fused wavefront vs layerwise chain) — single device
# ---------------------------------------------------------------------------

def tune_quantized_backend(n_x: int, n_h: int, n_layers: int, T: int, B: int,
                           *, tile: Optional[int] = None,
                           cache: Optional[ScheduleCache] = None,
                           iters: int = 3, warmup: int = 1,
                           measure: bool = True
                           ) -> Tuple[ScheduleEntry, List[ShmooRecord]]:
    """Measure the ``'fused'`` vs ``'layerwise'`` int8 stack decision that
    ``select_quantized_stack_backend`` hand-calibrates with
    ``_Q_FUSED_MIN_NH`` — the two launch shapes are bit-identical, so the
    trial only picks the faster one.  ``measure=False`` records the
    heuristic's own answer (``source='predicted'``) so a cold CI can still
    materialise a cache deterministically.
    """
    import jax
    from ..core import lstm, quant, systolic
    from ..core.lstm import _Q_FUSED_MIN_NH, _SEQ_MIN_T
    heuristic = ('fused' if (n_layers >= 2 and T >= _SEQ_MIN_T
                             and n_h >= _Q_FUSED_MIN_NH) else 'layerwise')
    records: List[ShmooRecord] = []
    from ..core.lstm import _VMEM_BUDGET_BYTES
    from ..kernels.lstm_seq import stack_vmem_bytes_estimate
    if stack_vmem_bytes_estimate(n_x, n_h, n_layers, B) > _VMEM_BUDGET_BYTES:
        # the fused kernel's resident working set does not fit — prune the
        # trial, the chain is the only admissible candidate
        entry = ScheduleEntry(kind='q_stack_backend', n_x=n_x, n_h=n_h,
                              n_layers=n_layers, T=T, B=B, mesh=ANY_MESH,
                              backend='layerwise', source='predicted')
        if cache is not None:
            cache.record(entry)
        return entry, records
    if not measure:
        entry = ScheduleEntry(kind='q_stack_backend', n_x=n_x, n_h=n_h,
                              n_layers=n_layers, T=T, B=B, mesh=ANY_MESH,
                              backend=heuristic, source='predicted')
        if cache is not None:
            cache.record(entry)
        return entry, records

    from ..kernels.lstm_seq import (lstm_layer_seq_quantized,
                                    lstm_stack_seq_quantized)
    tile = tile or min(n_h, 128)
    stack = lstm.init_lstm_stack(jax.random.PRNGKey(7), n_x, n_h, n_layers)
    qps = [systolic.quantize_packed(systolic.pack_lstm(
        lp, systolic.SystolicPlan(n_x if l == 0 else n_h, n_h, tile)))
        for l, lp in enumerate(stack.layers)]
    xs = jax.random.normal(jax.random.PRNGKey(8), (T, B, n_x)) * 0.5
    xs_q = quant.quantize(xs, quant.STATE_FMT)

    def chain(x):
        h = x
        for qp in qps:
            h = lstm_layer_seq_quantized(qp, h)
        return h

    f_lw = jax.jit(chain)
    f_fu = jax.jit(lambda x: lstm_stack_seq_quantized(qps, x))
    r_lw = np.asarray(jax.block_until_ready(f_lw(xs_q)))
    r_fu = np.asarray(jax.block_until_ready(f_fu(xs_q)))
    np.testing.assert_array_equal(r_lw, r_fu)   # bit-identical by contract
    us_lw, us_fu = measure_interleaved(
        [lambda: f_lw(xs_q), lambda: f_fu(xs_q)], iters=iters, warmup=warmup)
    backend = 'layerwise' if us_lw <= us_fu else 'fused'
    for name, us in (('layerwise', us_lw), ('fused', us_fu)):
        records.append(ShmooRecord(
            suite='q_stack_backend',
            params={'n_x': n_x, 'n_h': n_h, 'n_layers': n_layers, 'T': T,
                    'B': B, 'tile': tile, 'backend': name},
            metrics={'measured_us': us}))
    entry = ScheduleEntry(kind='q_stack_backend', n_x=n_x, n_h=n_h,
                          n_layers=n_layers, T=T, B=B, mesh=ANY_MESH,
                          backend=backend,
                          measured_us=min(us_lw, us_fu), source='measured',
                          host=host_fingerprint())
    if cache is not None:
        cache.record(entry)
    return entry, records


# ---------------------------------------------------------------------------
# Serving: materialise the entries the engine consults
# ---------------------------------------------------------------------------

def _serving_workload(n_in: int, slots: int, chunk: int
                      ) -> List[np.ndarray]:
    """Deterministic per-stream frame arrays for the serving-loop trial:
    ``slots`` streams of ``2.5 * chunk`` frames — long enough that every
    candidate steps multiple chunks AND hits a ragged tail (the packing /
    masking / retirement paths all execute), short enough to trial fast."""
    rng = np.random.RandomState(1234)
    n = 2 * chunk + max(1, chunk // 2)
    return [(rng.randn(n, n_in) * 0.5).astype(np.float32)
            for _ in range(slots)]


def tune_serving_config(cfg, *, chunk: int, slots: int,
                        cache: Optional[ScheduleCache] = None,
                        measure: bool = True, iters: int = 2,
                        params=None) -> List[ScheduleEntry]:
    """The ``launch/serve.py --tune`` entry point: record the cache entries
    serving dispatch consults for ``cfg``'s LSTM stack.

    (1) the int8 backend decision at the serving chunk shape (measured
    interleaved when ``measure``); (2) a chunk-depth ceiling for the
    deadline policy (``kind='stack_f32'``): the predicted-best ``Tc <=
    chunk`` for the paper's staged Table-2 placement — model-driven until
    a real staged measurement shadows it (exact keys beat wildcards);
    (3) when ``measure``, the END-TO-END SERVING-LOOP ceiling
    (``kind='serving_chunk'``): each candidate chunk depth drives a real
    ``StreamingEngine`` — packing, valid-length masking, admission,
    retirement, the full §7 loop, not just the kernel it launches — over a
    fixed deterministic workload, outputs asserted bit-equal across
    candidates (chunk boundaries are scheduling-only by the §7 contract)
    before the interleaved timing.  ``tuned_chunk_ceiling`` consults the
    measured entry FIRST; the kernel-level (2) stays the predicted
    fallback.  ``params`` defaults to the same deterministic init
    ``launch/serve.py`` uses.
    """
    n_x, n_h, L = cfg.lstm_inputs, cfg.lstm_hidden, cfg.n_layers
    entries = []
    ent, _ = tune_quantized_backend(n_x, n_h, L, chunk, slots, cache=cache,
                                    measure=measure, iters=iters)
    entries.append(ent)
    tcs = [t for t in TC_GRID if t <= chunk] or [chunk]
    stages = min(L, 3)
    cands = enumerate_staged_candidates(n_x, n_h, L, chunk, slots,
                                        stages=stages, rows=5, cols=5)
    cands = [c for c in cands if c.tc in tcs]
    if cands:
        ranked = rank_staged_candidates(cands, n_x, n_h, L, chunk)
        win, pred = ranked[0]
        ent = ScheduleEntry(kind='stack_f32', n_x=n_x, n_h=n_h, n_layers=L,
                            T=0, B=slots, mesh=ANY_MESH, tc=win.tc,
                            in_stage=win.in_stage, predicted_us=pred,
                            source='predicted')
        if cache is not None:
            cache.record(ent)
        entries.append(ent)
    if not measure:
        return entries

    # (3) time the real engine loop per candidate chunk depth.
    import jax
    from ..serving.engine import StreamingEngine
    if params is None:
        from ..models import get_bundle
        params, _ = get_bundle(cfg).init(jax.random.PRNGKey(0))
    streams = _serving_workload(n_x, slots, chunk)
    depths = sorted({t for t in TC_GRID if t < chunk} | {chunk})
    engines = [StreamingEngine(cfg, params, max_streams=slots, chunk=d)
               for d in depths]

    def run_once(eng):
        before = len(eng.sched.done)
        for f in streams:
            eng.submit(f)
        done = eng.run()[before:]
        return done

    outs = []
    for eng in engines:
        done = sorted(run_once(eng), key=lambda s: s.sid)
        outs.append([np.concatenate(s.log_probs) for s in done])
    for o in outs[1:]:   # §7: chunk boundaries are scheduling-only
        assert len(o) == len(outs[0])
        for a, b in zip(outs[0], o):
            np.testing.assert_array_equal(a, b)
    meds = measure_interleaved([lambda e=e: run_once(e) for e in engines],
                               iters=iters, warmup=0)
    win_i = int(np.argmin(meds))
    ent = ScheduleEntry(kind='serving_chunk', n_x=n_x, n_h=n_h, n_layers=L,
                        T=chunk, B=slots, mesh=ANY_MESH, tc=depths[win_i],
                        measured_us=meds[win_i], source='measured',
                        host=host_fingerprint())
    if cache is not None:
        cache.record(ent)
    entries.append(ent)
    return entries


# ---------------------------------------------------------------------------
# Deterministic offline replay
# ---------------------------------------------------------------------------

def replay_check(cache: ScheduleCache) -> int:
    """Verify the cache replays deterministically: every ``predicted``
    staged-schedule entry's winner is re-derivable from a fresh enumeration
    + ranking (no clocks, no RNG — same inputs, same winner), and every
    staged entry (measured included) sits inside today's admissible space.
    Returns the number of entries checked; raises AssertionError on drift.

    ``geometry`` entries (keyed ``'devices:N'``) are checked against a
    fresh geometry enumeration of the same budget: the recorded winner's
    (stages, rows, cols, blocks) must still be in the admissible space,
    and a ``predicted`` winner must re-rank first.
    """
    checked = 0
    for e in cache.entries():
        if e.kind == 'geometry' and e.mesh.startswith('devices:'):
            devices = int(e.mesh.split(':')[1])
            cands = enumerate_geometry_candidates(
                e.n_x, e.n_h, e.n_layers, e.T or 128, e.B or 8,
                devices=devices)
            geo = (e.stages, e.rows, e.cols,
                   tuple(int(p) for p in e.blocks.split(',')))
            assert any((c.stages, c.rows, c.cols, c.blocks) == geo
                       and c.tc <= (e.T or 128) for c in cands), \
                f'cached geometry left the admissible space: {e}'
            if e.source == 'predicted':
                ranked = rank_geometry_candidates(cands, e.n_x, e.n_h,
                                                  e.n_layers, e.T or 128)
                w = ranked[0][0]
                assert ((w.stages, w.rows, w.cols, w.blocks, w.tc,
                         w.in_stage)
                        == (geo[0], geo[1], geo[2], geo[3], e.tc,
                            e.in_stage)), \
                    f'predicted geometry winner drifted: {w} vs {e}'
            checked += 1
            continue
        if e.kind not in ('stack_f32', 'stack_int8') or not e.tc:
            continue
        if e.mesh == ANY_MESH or ':' not in e.mesh:
            continue            # family-wide ceilings have no single space
        dims = dict(p.split(':') for p in e.mesh.split(','))
        cands = enumerate_staged_candidates(
            e.n_x, e.n_h, e.n_layers, e.T or 128, e.B or 8,
            stages=int(dims.get('stage', 1)), rows=int(dims.get('row', 1)),
            cols=int(dims.get('col', 1)))
        # the dispatch-default chunk (ceil(T/4S), the geometry trial's
        # reference schedule) is admissible by construction even when it
        # falls off the TC_GRID shmoo grid
        default_tc = max(1, -(-(e.T or 128) // (4 * int(dims.get('stage',
                                                                 1)))))
        assert (any(c.tc == e.tc and c.in_stage == e.in_stage
                    for c in cands)
                or (cands and e.tc == default_tc
                    and any(c.in_stage == e.in_stage for c in cands))), \
            f'cached winner left the admissible space: {e}'
        if e.source == 'predicted':
            ranked = rank_staged_candidates(cands, e.n_x, e.n_h,
                                            e.n_layers, e.T or 128)
            win = ranked[0][0]
            assert (win.tc, win.in_stage) == (e.tc, e.in_stage), \
                f'predicted winner drifted: {(win.tc, win.in_stage)} vs {e}'
        checked += 1
    return checked
