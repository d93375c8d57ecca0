"""Packed multi-stream stateful streaming engine (DESIGN.md §7, §10, §11).

The deployment story of the paper — weights stay resident while audio frames
stream through — turned into a serving substrate: every active stream's
``(h, c)`` LSTM state lives in one packed per-layer state cache of shape
``(max_streams, N_h)``, and each engine step runs ONE batched chunked call to
the whole-sequence LSTM path (``core.lstm.lstm_stack_chunk``) for ALL active
streams.  On the ``pallas_seq`` backend the slot dimension maps onto the
batch-block (``bb``) grid of the persistent kernel, so every stream shares a
single weight DMA per chunk instead of paying one per slot (the E-PUR
amortisation); on ``pallas_seq_systolic`` the same call scales out over the
installed mesh.  Ragged streams are handled by the §7 valid-length masking
contract: a slot's padded tail steps are identity on its carried state, so
admission/eviction/refill never perturbs neighbouring streams.

Backend-agnostic by construction: the engine only speaks
``models.chipmunk_net.stream_forward``, which dispatches on
``cfg.lstm_backend`` (``xla_scan | pallas_seq | pallas_seq_fused |
pallas_seq_systolic | pallas_seq_fused_systolic`` via the installed mesh).
On ``pallas_seq_fused`` every engine step advances ALL active streams
through ALL stack layers in ONE wavefront kernel launch (DESIGN.md §8);
on ``pallas_seq_fused_systolic`` the same chunked call runs the staged
scale-out over the installed (stage, row, col) mesh (DESIGN.md §9).

Fault tolerance (DESIGN.md §10, ``runtime/serving_faults.py``): with a
``ServingFaultConfig`` attached, every engine step is driven by the
generalized ``FaultTolerantRunner`` — injected/real engine failures degrade
the backend down ``core.lstm.DEGRADATION_LADDER`` and elastically re-place
the packed cache (no stream loss, a logged latency blip); per-chunk
deadlines derived from the paper's real-time model are watched; a fused
non-finite guard quarantines exactly the poisoned slot; and
preempted/evicted streams checkpoint their packed ``(h, c)`` rows + frame
cursor so a resubmitted stream resumes **bit-equal** to an uninterrupted
run.

Async double-buffered dispatch (DESIGN.md §11): every step is split into a
non-blocking LAUNCH (the jitted chunk call is dispatched and its device
futures recorded) and a later COMMIT (``block_until_ready``, fault/deadline
handling, quarantine scrub, cursor advance, retirement, refill).  The
synchronous mode commits immediately after launching — a depth-0 pipeline —
so both modes share one code path and stay bit-equal by construction.  With
``async_dispatch=True`` the commit of chunk k is deferred: while the device
computes chunk k, the host speculatively plans, packs, and launches chunk
k+1 on top of chunk k's un-committed output-state futures (retirements and
FIFO/priority admissions are deterministic at launch time, so the
speculation is exact on the clean path).  If chunk k's commit deviates —
engine fault, quarantine, or a scheduler decision the speculation could not
see — the speculative launch is SQUASHED (its results are never observed)
and the next step relaunches from committed state, preserving PR 6's
commit-on-success discipline: nothing (states, cursors, outputs,
checkpoints) commits until the in-flight chunk resolves.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import chipmunk_net
from ..runtime.fault import FaultConfig, FaultTolerantRunner, RingLog
from ..runtime.recovery import MeshHealthTracker, build_rungs
from ..runtime.serving_faults import (ChunkSizePolicy, EngineFailure,
                                      ServingFaultConfig,
                                      StreamStateCheckpointer,
                                      elastic_replace, finite_slots)
from .scheduler import SlotScheduler
from .session import IncrementalCTCDecoder, StreamSession
from .spans import span

# launch-to-commit wall times kept for ``stats()['p50_chunk_s']``
CHUNK_WALLS_KEPT = 4096


def tuned_chunk_ceiling(cfg, chunk: int, max_streams: int) -> int:
    """Chunk-length ceiling for the deadline policy, cache-first.

    The ``ChunkSizePolicy`` adapts chunk length at runtime but needs a
    CEILING to grow back toward; historically that was just the engine's
    packing width.  With a schedule cache installed (``repro.tune``), a
    tuned chunk depth clamps it: chunks deeper than the measured-best
    ``Tc`` only add latency without throughput.  Two entry kinds, most
    trustworthy first: ``'serving_chunk'`` — the END-TO-END serving-loop
    measurement ``tune_serving_config`` records (the engine step with
    packing/masking/admission, exactly what this ceiling governs) — then
    the kernel-level ``'stack_f32'`` prediction as the fallback (exact
    ``(T, B)`` keys first, wildcards after).  Scheduling-only by the §11
    contract — outputs are bit-invariant to where chunk boundaries fall.
    Returns ``chunk`` unchanged on a cache miss (or ``tc=0`` entry).
    """
    from ..core.systolic import current_mesh
    from ..tune.schedule import current_schedule_cache, mesh_signature
    cache = current_schedule_cache()
    if cache is None:
        return chunk
    ent = cache.lookup('serving_chunk', n_x=cfg.lstm_inputs,
                       n_h=cfg.lstm_hidden, n_layers=cfg.n_layers,
                       T=chunk, B=max_streams,
                       mesh=mesh_signature(current_mesh()))
    if ent is None or not ent.tc:
        ent = cache.lookup('stack_f32', n_x=cfg.lstm_inputs,
                           n_h=cfg.lstm_hidden, n_layers=cfg.n_layers,
                           T=chunk, B=max_streams,
                           mesh=mesh_signature(current_mesh()))
    if ent is not None and ent.tc:
        return max(1, min(chunk, int(ent.tc)))
    return chunk


@dataclasses.dataclass
class _InFlight:
    """One launched-but-uncommitted chunk: the device futures of the jitted
    call plus everything a retry needs to recompute it from committed state
    (the frames/valid arrays and the poison edit).  Bookkeeping only — no
    arithmetic of its own."""

    chunk_idx: int                       # logical step index of this chunk
    active: List[Tuple[int, StreamSession]]   # (slot, session) at launch
    valid: np.ndarray                    # (S,) valid frame counts
    frames_j: jax.Array                  # (S, chunk_len, n_in) device input
    valid_j: jax.Array
    poison_slot: Optional[int]           # injected NaN edit to re-apply
    lp: jax.Array                        # device futures of the chunk call
    new_states: tuple
    finite: jax.Array
    t_launch: float
    chunk_len: int
    states_in: tuple = ()                # inputs as fed (incl. poison edit)


class StreamingEngine:
    """Continuous streaming over a packed slot grid of recurrent state.

    One instance owns ``max_streams`` state slots; streams are admitted from
    a priority/FIFO queue, advance up to ``chunk`` frames per ``step``
    through one batched call, and are retired when their frames are
    exhausted.  Numerics contract: a stream's emitted log-probs equal the
    monolithic ``chipmunk_net.forward`` of its full utterance on the same
    backend (bit-equal on a fixed backend code path; allclose across
    backends), regardless of which streams shared its batch
    (tests/test_streaming.py) — and regardless of ``async_dispatch`` and of
    where the chunk-size policy moves chunk boundaries: the async engine's
    outputs are bit-equal to the sync engine's for every admission/
    eviction/preemption/fault schedule (tests/test_serving_async.py).
    A preempted stream resumed from its checkpoint continues bit-equal to
    an uninterrupted run (tests/test_serving_faults.py).

    ``faults`` (a ``runtime.ServingFaultConfig``) opts into the §10 fault
    model: deterministic engine-failure injection + ladder degradation,
    per-chunk deadline watchdog, non-finite slot quarantine, and stream
    checkpoint/resume through ``CheckpointManager``.  Without it the engine
    behaves exactly as before (no guard, no runner — zero overhead).

    ``async_dispatch`` enables §11 double buffering (launch of chunk k+1
    overlapped with device compute of chunk k); ``chunk_policy`` (a
    ``runtime.ChunkSizePolicy``) makes the per-step chunk length adapt to
    the observed launch-to-commit wall time against the paper's 10 ms
    frame-arrival budget.  Both are scheduling-only: outputs are
    bit-invariant to them.
    """

    def __init__(self, cfg, params, *, max_streams: int = 4, chunk: int = 16,
                 decode_ctc: bool = False,
                 faults: Optional[ServingFaultConfig] = None,
                 async_dispatch: bool = False,
                 chunk_policy: Optional[ChunkSizePolicy] = None):
        assert cfg.family == 'lstm', (
            'StreamingEngine serves the stateful recurrent family; token '
            'families keep the per-slot decode loop (launch/serve.py)')
        assert chunk >= 1 and max_streams >= 1
        from ..core.lstm import resolve_serving_backend
        self.params = params
        self.chunk = chunk
        self.decode_ctc = decode_ctc
        self.async_dispatch = bool(async_dispatch)
        self._policy = chunk_policy
        if chunk_policy is not None:
            assert chunk_policy.chunk_max <= chunk, (
                'chunk_policy.chunk_max exceeds the engine packing width',
                chunk_policy.chunk_max, chunk)
        # pin ONE concrete backend per engine (the §7 bit-equality contract
        # holds per backend code path; the ladder needs a known rung)
        self.backend = resolve_serving_backend(
            params, cfg.lstm_backend, chunk, max_streams)
        self.cfg = cfg.replace(lstm_backend=self.backend)
        self.sched: SlotScheduler[StreamSession] = SlotScheduler(max_streams)
        self.states = tuple(
            (jnp.zeros((max_streams, cfg.lstm_hidden), cfg.dtype()),
             jnp.zeros((max_streams, cfg.lstm_hidden), cfg.dtype()))
            for _ in range(cfg.n_layers))
        self._next_sid = 0
        self._step_idx = 0
        self._pending: Optional[_InFlight] = None
        self._poison_recorded: set = set()
        self.chunk_walls: Deque[float] = deque(maxlen=CHUNK_WALLS_KEPT)
        self.events = RingLog(faults.event_log_cap
                              if faults is not None else None)

        self.faults = faults
        if faults is not None:
            self._guard = faults.guard_nonfinite
            self._ckpt = (StreamStateCheckpointer(faults.checkpoint_dir)
                          if faults.checkpoint_dir else None)
            self._runner: Optional[FaultTolerantRunner] = FaultTolerantRunner(
                cfg=FaultConfig(max_retries=faults.max_retries,
                                backoff_s=faults.backoff_s,
                                deadline_s=faults.resolve_deadline_s(chunk),
                                heartbeat_path=faults.heartbeat_path,
                                event_log_cap=faults.event_log_cap),
                fail_schedule=faults.make_fail_schedule())
            # §14 recovery runtime: materialise the rung ladder for this
            # deployment (die-mesh rungs when a two-level mesh is installed)
            # and track fault-domain health against it
            from ..launch.mesh import current_die_mesh
            self._rungs = build_rungs(
                self.backend, n_layers=cfg.n_layers, n_h=cfg.lstm_hidden,
                die_mesh=current_die_mesh(), n_x=cfg.lstm_inputs,
                T=chunk, batch=max_streams)
            if self._rungs[0].backend != self.backend:
                self._rungs = build_rungs(
                    self.backend, n_layers=cfg.n_layers,
                    n_h=cfg.lstm_hidden, n_x=cfg.lstm_inputs,
                    T=chunk, batch=max_streams)
            self._tracker: Optional[MeshHealthTracker] = MeshHealthTracker(
                n_domains=self._rungs[0].need,
                hysteresis=faults.promote_hysteresis)
        else:
            self._guard = False
            self._ckpt = None
            self._runner = None
            self._rungs = ()
            self._tracker = None
        self._rung_idx = 0
        self._healed_steps: set = set()
        self._last_commit: Optional[dict] = None   # canary replay material
        from ..core.systolic import current_mesh
        self._home_mesh = current_mesh()   # re-installed on mesh promotions
        self._build_admission()
        self._build_fwd()

    def _make_fwd(self, cfg):
        """Jitted packed chunk call for ``cfg``'s backend.  The non-finite
        guard is fused into the same jit (one reduction over the new
        states, no extra dispatch); with the guard off an all-ones constant
        is returned, so the clean path's arithmetic is unchanged.  Also the
        factory the promotion canary uses to build the CANDIDATE backend's
        call without touching the incumbent's."""
        guard = self._guard

        def fwd(params, states, frames, valid):
            lp, new_states = chipmunk_net.stream_forward(
                cfg, params, states, frames, valid_len=valid)
            if guard:
                finite = finite_slots(new_states)
            else:
                finite = jnp.ones((frames.shape[0],), bool)
            return lp, new_states, finite

        return jax.jit(fwd)

    def _build_admission(self):
        """The jitted slot initialisations of ``_apply_admissions``, each
        one device call of a fixed shape: ``_reset_slots(states, mask)``
        zeroes every row whose ``(S,)`` boolean ``mask`` entry is set (exact
        zeros, as ``.at[slot].set(0)`` writes), so admitting 1 or all S
        streams reuses one compiled reset; ``_restore_slot(states, slot,
        rows)`` writes one resumed stream's saved per-layer ``(h, c)`` rows
        into ``slot``.  The inputs are not donated: an in-flight chunk's
        ``states_in`` and the canary's replay material may still hold
        them."""
        def reset(states, mask):
            return jax.tree.map(
                lambda a: jnp.where(mask[:, None], jnp.zeros((), a.dtype), a),
                states)

        def restore(states, slot, rows):
            return tuple((h.at[slot].set(rh), c.at[slot].set(rc))
                         for (h, c), (rh, rc) in zip(states, rows))

        self._reset_slots = jax.jit(reset)
        self._restore_slot = jax.jit(restore)

    def _build_fwd(self):
        """(Re)build the jitted packed chunk call for the CURRENT backend.
        Called at construction and after every rung change (degradation or
        promotion)."""
        self._fwd = self._make_fwd(self.cfg)

    # ------------------------------------------------------------ admission
    def submit(self, frames: np.ndarray, sid: Optional[int] = None,
               priority: int = 0) -> StreamSession:
        """Queue an utterance ((L, n_in) host frames) for streaming.
        ``priority`` > 0 marks a latency-SLO stream: it is admitted ahead
        of bulk streams and may displace one (§11 priority admission)."""
        with span('submit'):
            frames = np.asarray(frames, np.float32)
            assert (frames.ndim == 2
                    and frames.shape[1] == self.cfg.lstm_inputs), frames.shape
            if sid is None:
                sid = self._next_sid
            self._next_sid = max(self._next_sid, sid + 1)
            dec = IncrementalCTCDecoder() if self.decode_ctc else None
            sess = StreamSession(sid=sid, frames=frames, decoder=dec,
                                 priority=priority, t_enqueue=time.time())
            self.sched.submit(sess)
        return sess

    def _apply_admissions(self, states: tuple,
                          admissions: List[Tuple[int, StreamSession]]
                          ) -> tuple:
        """Functional slot initialisation, shared by the realized admission
        (``_refill``) and the §11 speculative launch composition: a recycled
        slot must never leak its previous occupant's state.  Every fresh
        stream's packed rows are zeroed by ONE ``_reset_slots`` call over an
        ``(S,)`` slot mask, whatever the number admitted; a resumed session
        then gets its saved per-layer ``(h, c)`` rows scattered back in by
        one ``_restore_slot`` call each (an exact host round-trip, so resume
        is bit-equal to never having been evicted).  Pure functional update
        — both call sites perform the identical op sequence, which is what
        keeps the speculative input bit-equal to the committed one."""
        fresh = [slot for slot, sess in admissions
                 if sess.saved_state is None]
        if fresh:
            mask = np.zeros((self.sched.num_slots,), bool)
            mask[fresh] = True
            states = self._reset_slots(states, mask)
        for slot, sess in admissions:
            if sess.saved_state is not None:
                states = self._restore_slot(states, np.int32(slot),
                                            sess.saved_state)
        return states

    @staticmethod
    def _admission_dispatches(admissions) -> int:
        """Device calls ``_apply_admissions`` makes for ``admissions``: one
        reset when any stream is fresh, plus one restore per resumed one."""
        resumed = sum(sess.saved_state is not None for _, sess in admissions)
        return int(resumed < len(admissions)) + resumed

    def _refill(self) -> List[Tuple[int, StreamSession]]:
        """Admit pending streams into free slots (``sched.refill``), then
        initialise their rows of the committed cache in one batch
        (``_apply_admissions``) and clear the resumed sessions' saved rows,
        inside a ``serving.admit`` span whose counters are ``streams``, the
        number admitted, and ``dispatches``, the device calls that took."""
        with span('admit') as sp:
            admitted = self.sched.refill()
            sp.set_metadata(streams=len(admitted),
                            dispatches=self._admission_dispatches(admitted))
            self.states = self._apply_admissions(self.states, admitted)
            for slot, sess in admitted:
                if sess.saved_state is not None:
                    sess.saved_state = None
                    self._record('resume', sid=sess.sid, slot=slot,
                                 cursor=sess.cursor)
        return admitted

    def _snapshot_slot(self, slot: int) -> tuple:
        """Host copy of one slot's per-layer ``(h, c)`` rows — the stream's
        packed state, exactly as carried (bit-preserving numpy transfer, no
        arithmetic)."""
        return tuple((np.asarray(h[slot]), np.asarray(c[slot]))
                     for h, c in self.states)

    def preempt(self, sid: int, requeue: bool = True
                ) -> Optional[StreamSession]:
        """Preempt a stream: snapshot its packed per-layer ``(h, c)`` rows +
        frame cursor onto the session (and through the stream checkpointer
        when one is configured), free its slot, and — with ``requeue=True``
        — re-enter it at the front of its priority class in the pending
        queue.  The resumed stream continues **bit-equal** to an
        uninterrupted run on the same backend (tests/test_serving_faults.py,
        tests/test_serving_async.py).  A control-plane op: under async
        dispatch the in-flight chunk is committed first (``_sync``), so the
        snapshot always reads committed rows.  Returns the session, or
        None when ``sid`` is not active."""
        self._sync()
        for slot, sess in self.sched.active():
            if sess.sid == sid:
                sess.saved_state = self._snapshot_slot(slot)
                if self._ckpt is not None:
                    self._ckpt.save(sess.sid, sess.saved_state, sess.cursor)
                    self._record('checkpoint', sid=sid, cursor=sess.cursor)
                self.sched.evict(slot, requeue=requeue)
                self._record('preempt', sid=sid, slot=slot, requeue=requeue)
                return sess
        return None

    def evict(self, sid: int) -> Optional[StreamSession]:
        """Abandon a stream mid-flight; its slot is freed for refill.

        Neighbouring streams are untouched — their state rows are separate
        slots of the packed cache and the freed row is zeroed on the next
        admission (``_apply_admissions``).  The evicted stream's state is no
        longer silently discarded: its ``(h, c)`` rows + cursor are
        snapshotted onto the session (and to disk when a checkpointer is
        configured), so ``resume``/``resume_from_checkpoint`` can continue
        it later, bit-equal."""
        return self.preempt(sid, requeue=False)

    def resume(self, sess: StreamSession) -> StreamSession:
        """Resubmit a preempted/evicted session; it re-enters the pending
        queue and, on admission, restores its saved packed state and
        continues from its cursor — bit-equal to an uninterrupted run on
        the same backend."""
        assert sess.error is None, f'stream {sess.sid} was quarantined'
        self.sched.submit(sess)
        return sess

    def resume_from_checkpoint(self, frames: np.ndarray, sid: int
                               ) -> StreamSession:
        """Rebuild a stream from its on-disk checkpoint and submit it.

        ``frames`` is the full utterance (inputs are not checkpointed —
        only the packed per-layer ``(h, c)`` rows and the frame cursor);
        the session resumes at the checkpointed cursor and its emitted
        log-probs continue from there, bit-equal to the uninterrupted
        run's suffix on the same backend."""
        assert self._ckpt is not None, 'no checkpoint_dir configured'
        frames = np.asarray(frames, np.float32)
        n_h = self.cfg.lstm_hidden
        like = tuple(
            (np.zeros((n_h,), h.dtype), np.zeros((n_h,), c.dtype))
            for h, c in self.states)
        state_rows, cursor = self._ckpt.load(sid, like)
        dec = IncrementalCTCDecoder() if self.decode_ctc else None
        sess = StreamSession(sid=sid, frames=frames, decoder=dec,
                             cursor=cursor, t_enqueue=time.time())
        sess.saved_state = tuple(
            (np.asarray(rh), np.asarray(rc)) for rh, rc in state_rows)
        self._next_sid = max(self._next_sid, sid + 1)
        self.sched.submit(sess)
        self._record('resume_from_checkpoint', sid=sid, cursor=cursor)
        return sess

    # -------------------------------------------------------- fault hooks
    def _record(self, kind: str, **info) -> None:
        self.events.append({'kind': kind, 'step': self._step_idx, **info})

    def _install_rung_mesh(self, rung) -> None:
        """Point the process mesh registry at ``rung``'s topology: the
        healthy dies' flattened submesh for a die rung, the construction-
        time home mesh for a meshless systolic rung, no mesh for a flat
        rung.  Placement only — the §7 contract keeps outputs bit-equal."""
        from ..core import systolic
        if rung.n_dies is not None:
            from ..launch.mesh import current_die_mesh
            dm = current_die_mesh()
            use = self._tracker.healthy[:rung.n_dies]
            systolic.install_mesh(dm.submesh(use))
        elif rung.backend.endswith('_systolic'):
            systolic.install_mesh(self._home_mesh)
        else:
            systolic.clear_mesh()

    def _on_engine_fault(self, exc: BaseException, attempt: int) -> None:
        """Between a failed chunk attempt and its retry: transient faults
        (including ``EngineFailure(transient=True)``) just retry; a
        permanent ``EngineFailure`` marks its fault domain dead in the
        health tracker and degrades to the highest rung the surviving
        capacity supports (at least one rung down) — re-forming the die
        mesh on the healthy dies, or uninstalling a broken flat mesh —
        and elastically re-places the packed state cache on the surviving
        topology (bit-preserving host round-trip) before the retry
        recomputes the SAME chunk.  No stream loses state or frames."""
        if not isinstance(exc, EngineFailure) or exc.transient:
            return                          # transient: plain retry
        killed = self._tracker.fail(self._step_idx, domain=exc.domain,
                                    n_dead=exc.n_dead)
        domain = killed[0] if killed else exc.domain
        n = self._tracker.n_healthy
        supported = next(
            (i for i, r in enumerate(self._rungs) if r.need <= n),
            len(self._rungs) - 1)
        target = max(self._rung_idx + 1, supported)
        if target >= len(self._rungs):
            self._record('degrade_exhausted', backend=self.backend,
                         n_dead=exc.n_dead)
            return                          # bottom of the ladder: retry as-is
        prev = self.backend
        rung = self._rungs[target]
        self._install_rung_mesh(rung)
        self.backend = rung.backend
        self.cfg = self.cfg.replace(lstm_backend=rung.backend)
        self.states = elastic_replace(self.states)
        self._build_fwd()
        self._rung_idx = target
        self._last_commit = None            # stale incumbent evidence
        self._record('degrade', from_backend=prev, to_backend=rung.backend,
                     n_dead=exc.n_dead, domain=domain)

    def _quarantine(self, active, finite, new_states) -> tuple:
        """Quarantine every active slot whose new carried state went
        non-finite: zero exactly that slot's rows, evict the session with a
        terminal ``error`` (never retired into ``done``, never requeued),
        and leave every neighbouring slot's rows and outputs bit-untouched.
        Returns the scrubbed states."""
        for slot, sess in active:
            if not finite[slot]:
                new_states = jax.tree.map(
                    lambda a: a.at[slot].set(0), new_states)
                sess.error = (f'non-finite state quarantined at engine '
                              f'step {self._step_idx}')
                sess.saved_state = None
                self.sched.evict(slot)
                self._record('quarantine', sid=sess.sid, slot=slot)
        return new_states

    # -------------------------------------------------- launch/commit core
    def _next_chunk_len(self) -> int:
        """Frames the next chunk should carry: the policy's current size
        (never above the engine packing width), else the fixed ``chunk``."""
        if self._policy is not None:
            return min(self._policy.size, self.chunk)
        return self.chunk

    def _deadline_for(self, chunk_len: int) -> Optional[float]:
        """Per-chunk deadline for the watchdog: the chunk-size policy's
        arrival-rate budget when one is attached (so ``deadline_miss``
        events and the policy's feedback agree), else the fault config's
        ``resolve_deadline_s`` for THIS chunk length."""
        if self._policy is not None:
            return self._policy.budget_s(chunk_len)
        if self.faults is not None:
            return self.faults.resolve_deadline_s(chunk_len)
        return None

    def _pack(self, plan, chunk_len: int):
        """Host-side packing of one chunk: gather each planned stream's next
        frames at its planned cursor into the (S, chunk_len, n_in) batch
        buffer.  ``plan`` rows are (slot, session, cursor) — the cursor is
        explicit so the async path can pack SPECULATIVELY (committed cursor
        + in-flight valid count) without mutating any session."""
        S = self.sched.num_slots
        frames = np.zeros((S, chunk_len, self.cfg.lstm_inputs), np.float32)
        valid = np.zeros((S,), np.int32)
        for slot, sess, cursor in plan:
            part = sess.frames[cursor:cursor + chunk_len]
            frames[slot, :len(part)] = part
            valid[slot] = len(part)
        return frames, valid

    def _launch(self, states_in: tuple, plan, chunk_idx: int,
                chunk_len: int) -> _InFlight:
        """Dispatch one chunk WITHOUT blocking: compose the injected poison
        edit (``faults.poison_at``) onto the input states, pack the planned
        streams' frames, and fire the jitted chunk call — its results stay
        device futures inside the returned ``_InFlight`` record until
        ``_commit`` resolves them.  Nothing engine-visible mutates here."""
        poison = (self.faults.poison_at.get(chunk_idx)
                  if self.faults is not None else None)
        if poison is not None:
            states_in = jax.tree.map(
                lambda a: a.at[poison].set(jnp.nan), states_in)
            if chunk_idx not in self._poison_recorded:
                self._poison_recorded.add(chunk_idx)
                self._record('poison_injected', slot=poison, step=chunk_idx)
        with span('pack'):
            frames, valid = self._pack(plan, chunk_len)
        with span('dispatch'):
            frames_j, valid_j = jnp.asarray(frames), jnp.asarray(valid)
            t0 = time.time()
            lp, st, fin = self._fwd(self.params, states_in, frames_j,
                                    valid_j)
        return _InFlight(chunk_idx=chunk_idx,
                         active=[(i, s) for i, s, _ in plan],
                         valid=valid, frames_j=frames_j, valid_j=valid_j,
                         poison_slot=poison, lp=lp, new_states=st,
                         finite=fin, t_launch=t0, chunk_len=chunk_len,
                         states_in=states_in)

    def _commit(self, rec: _InFlight) -> bool:
        """Resolve one in-flight chunk and commit it: block on the device
        futures (under the fault runner when configured — injected failures
        discard the launched futures and retry with a fresh synchronous
        recompute from COMMITTED state, after the ladder degradation), feed
        the launch-to-commit wall time to the deadline watchdog and the
        chunk-size policy, scrub quarantined slots, then — only now —
        advance states, cursors, outputs, and retirement.  Returns True iff
        the commit was clean (no fault, no quarantine): the async path may
        adopt its speculative launch only then."""
        deadline = self._deadline_for(rec.chunk_len)

        def resolve():
            with span('wait'):
                jax.block_until_ready(rec.lp)
            with span('fetch'):
                return (np.asarray(rec.lp), rec.new_states,
                        np.asarray(rec.finite))

        def retry():
            # the failed futures are dead; recompute synchronously from the
            # committed cache (admissions for this chunk are already
            # realized in it; only the injected poison edit is re-applied)
            states_in = self.states
            if rec.poison_slot is not None:
                states_in = jax.tree.map(
                    lambda a: a.at[rec.poison_slot].set(jnp.nan), states_in)
            with span('dispatch'):
                lp, st, fin = self._fwd(self.params, states_in,
                                        rec.frames_j, rec.valid_j)
            with span('wait'):
                jax.block_until_ready(lp)
            with span('fetch'):
                return np.asarray(lp), st, np.asarray(fin)

        faulted = False
        if self._runner is not None:
            n_before = sum(1 for e in self._runner.events
                           if e['kind'] == 'fault')
            host, new_states, finite = self._runner.run(
                rec.chunk_idx, resolve, on_fault=self._on_engine_fault,
                retry_fn=retry, launched_at=rec.t_launch,
                deadline_s=deadline)
            faulted = sum(1 for e in self._runner.events
                          if e['kind'] == 'fault') > n_before
        else:
            host, new_states, finite = resolve()
        dt = time.time() - rec.t_launch
        self.chunk_walls.append(dt)
        if self._policy is not None:
            self._policy.observe(rec.chunk_len, dt)
            if self._runner is None and dt > self._policy.budget_s(
                    rec.chunk_len):
                self._record('deadline_miss', dt=dt,
                             deadline_s=self._policy.budget_s(rec.chunk_len))

        quarantined = not bool(finite.all())
        if quarantined:
            new_states = self._quarantine(rec.active, finite, new_states)
        self.states = new_states
        with span('deliver'):
            for i, sess in rec.active:
                if sess.error is not None:      # quarantined this step
                    continue
                sess.consume(host[i, :rec.valid[i]])
                if sess.remaining == 0:
                    sess.t_done = time.time()
                    self.sched.finish(i)
        self._step_idx += 1
        clean = not (quarantined or faulted)
        if clean and self._tracker is not None and self._rung_idx > 0:
            # canary replay material: host copies of exactly what this
            # commit consumed and produced (captured only while degraded —
            # the home rung pays nothing)
            self._last_commit = {
                'states_in': jax.tree.map(np.asarray, rec.states_in),
                'frames': np.asarray(rec.frames_j),
                'valid': np.asarray(rec.valid_j),
                'lp': host,
                'new_states': jax.tree.map(np.asarray, new_states),
            }
        return clean

    def _sync(self) -> None:
        """Async control-plane barrier: commit the in-flight chunk, if any.
        Preemption/eviction snapshots and checkpoint saves must read
        COMMITTED state, so every control op drains the pipeline first.
        No-op in sync mode (nothing is ever left in flight)."""
        if self._pending is not None:
            rec, self._pending = self._pending, None
            self._commit(rec)

    # ------------------------------------------------- recovery / promotion
    def _poll_recovery(self) -> None:
        """Top-of-step recovery poll (§14): apply any scheduled heals
        (``faults.recover_at``, each engine step at most once) to the
        health tracker, then attempt a canary-validated promotion when
        capacity and the hysteresis window allow.  Keyed on the COMMITTED
        step index in both dispatch modes, so sync and async replay the
        same recovery trail."""
        if self._tracker is None:
            return
        heal_n = self.faults.recover_at.get(self._step_idx)
        if heal_n and self._step_idx not in self._healed_steps:
            self._healed_steps.add(self._step_idx)
            revived = self._tracker.heal(self._step_idx, heal_n)
            self._record('heal', domains=list(revived),
                         n_healed=int(heal_n))
        if self._rung_idx > 0:
            self._maybe_promote()

    def _canary_equal(self, a, b) -> bool:
        """Canary comparison: bitwise by default (``np.array_equal`` on host
        copies — the §6/§9 rungs of one arithmetic class really are
        bit-equal), or allclose under an explicit ``canary_rtol`` opt-in
        for cross-class promotions."""
        rtol = self.faults.canary_rtol
        if rtol is None:
            return bool(np.array_equal(np.asarray(a), np.asarray(b)))
        return bool(np.allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                atol=0.0))

    def _maybe_promote(self) -> None:
        """Attempt one climb-back step up the rung ladder (§14).

        Preconditions: the candidate rung (one above current) is within the
        tracker's healthy capacity, the hysteresis window is open, and —
        when the canary is armed — a committed chunk exists to validate
        against.  The pipeline is drained first (``_sync``), so a promotion
        NEVER lands mid-flight; the drain may itself fault or move rungs,
        so every precondition is re-checked after it.

        Canary protocol: install the candidate topology, build the
        candidate backend's jitted call, replay the last committed chunk as
        a SHADOW against a copy of the committed input state, and compare
        the replayed log-probs AND new states against the incumbent's
        committed results on the host.  Equal -> promote: re-shard the
        packed session cache onto the (larger) candidate topology
        (``elastic_replace`` — the upward inverse of the degrade path's
        shrink), adopt the candidate call, and re-arm the hysteresis
        window.  Unequal -> reject: restore the incumbent topology
        untouched, emit ``promote_rejected``, and double the backoff.
        Commit-on-success end to end: no engine-visible state changes
        unless the canary passes."""
        step = self._step_idx
        if (self._tracker.n_healthy < self._rungs[self._rung_idx - 1].need
                or not self._tracker.can_promote(step)):
            return
        if self.faults.canary and self._last_commit is None:
            return                  # nothing committed to validate against
        self._sync()                # promotion never lands mid-flight
        if self._rung_idx == 0:
            return
        cand_idx = self._rung_idx - 1
        cand = self._rungs[cand_idx]
        if (self._tracker.n_healthy < cand.need
                or not self._tracker.can_promote(self._step_idx)):
            return
        lc = self._last_commit
        if self.faults.canary and lc is None:
            return
        from ..core import systolic
        prev_mesh = systolic.current_mesh()
        self._install_rung_mesh(cand)
        cand_cfg = self.cfg.replace(lstm_backend=cand.backend)
        cand_fwd = self._make_fwd(cand_cfg)
        if self.faults.canary:
            self._record('promote_canary', from_backend=self.backend,
                         to_backend=cand.backend, chunk=lc['lp'].shape[-2]
                         if lc['lp'].ndim >= 2 else 0)
            states_in = jax.tree.map(jnp.asarray, lc['states_in'])
            lp, st, _ = cand_fwd(self.params, states_in,
                                 jnp.asarray(lc['frames']),
                                 jnp.asarray(lc['valid']))
            ok = self._canary_equal(jax.block_until_ready(lp), lc['lp'])
            ref_leaves = jax.tree.leaves(lc['new_states'])
            got_leaves = jax.tree.leaves(st)
            ok = ok and len(ref_leaves) == len(got_leaves) and all(
                self._canary_equal(g, r)
                for g, r in zip(got_leaves, ref_leaves))
            if not ok:
                # squash: restore the incumbent topology, nothing committed
                if prev_mesh is not None:
                    systolic.install_mesh(prev_mesh)
                else:
                    systolic.clear_mesh()
                self._tracker.note_reject(self._step_idx)
                self._record('promote_rejected', from_backend=self.backend,
                             to_backend=cand.backend,
                             backoff=self._tracker.backoff)
                return
        prev = self.backend
        self.backend = cand.backend
        self.cfg = cand_cfg
        self.states = elastic_replace(self.states)
        self._fwd = cand_fwd
        self._rung_idx = cand_idx
        if cand_idx == 0:
            self._last_commit = None    # home rung: stop paying capture
        self._tracker.note_promote(self._step_idx)
        self._record('promote', from_backend=prev, to_backend=cand.backend,
                     n_dies=cand.n_dies,
                     healthy=list(self._tracker.healthy))

    def _maybe_priority_preempt(self) -> None:
        """§11 priority admission: when every slot is busy and a strictly
        higher-priority stream waits, preempt the scheduler's candidate
        (checkpoint rows + cursor, requeue within its class) so the next
        refill admits the SLO stream.  Scheduling only — the displaced
        stream later resumes bit-equal (§10)."""
        slot = self.sched.preempt_candidate()
        if slot is not None:
            sess = self.sched.slots[slot]
            self.preempt(sess.sid)

    def _speculate(self, rec: _InFlight):
        """Plan chunk k+1 while chunk k is still in flight (no mutation).

        Retirements and admissions are deterministic at launch time: a
        stream retires iff its remaining frames minus the in-flight valid
        count hit zero, and refill admits the pending queue (already in
        priority order) into free slots in slot order.  Returns ``(plan,
        admissions)`` — plan rows are (slot, session, speculative cursor) —
        or None when the next step cannot be speculated: nothing to run, a
        scheduled engine failure on the in-flight chunk (its commit will
        deviate), or a waiting stream that outranks an active one (the
        commit-time priority preemption must run serialized)."""
        if self.faults is not None and rec.chunk_idx in self.faults.fail_at:
            return None
        survivors, freeing = [], []
        for slot, sess in rec.active:
            if sess.remaining - int(rec.valid[slot]) > 0:
                survivors.append((slot, sess,
                                  sess.cursor + int(rec.valid[slot])))
            else:
                freeing.append(slot)
        free = sorted({i for i, s in enumerate(self.sched.slots)
                       if s is None} | set(freeing))
        queue = list(self.sched.pending)
        if queue and not free:
            low = min(s.priority for _, s, _ in survivors)
            if max(q.priority for q in queue) > low:
                return None
        admissions = []
        for slot in free:
            if not queue:
                break
            admissions.append((slot, queue.pop(0)))
        plan = survivors + [(slot, sess, sess.cursor)
                            for slot, sess in admissions]
        plan.sort(key=lambda row: row[0])
        if not plan:
            return None
        return plan, admissions

    # ------------------------------------------------------------- stepping
    def _step_async(self) -> bool:
        """One §11 double-buffered step: speculatively pack + launch chunk
        k+1 on the in-flight chunk k's output-state futures (host work and
        device compute overlap here), then commit chunk k; adopt the
        speculative launch only if the commit was clean AND the realized
        admissions match the speculation, else squash it.  The speculative
        admissions run in a ``serving.admit`` span with a ``dispatches``
        counter and no ``streams`` counter: the realized refill counts each
        admitted stream once."""
        if self._pending is None:
            # pipeline fill: plan from committed state, launch, don't commit
            self._maybe_priority_preempt()
            self._refill()
            plan = [(i, s, s.cursor) for i, s in self.sched.active()]
            if not plan:
                return False
            self._pending = self._launch(self.states, plan, self._step_idx,
                                         self._next_chunk_len())
            return True
        rec, self._pending = self._pending, None
        spec = self._speculate(rec)
        spec_rec = None
        if spec is not None:
            plan, admissions = spec
            states_in = rec.new_states
            with span('admit') as sp:
                sp.set_metadata(
                    dispatches=self._admission_dispatches(admissions))
                states_in = self._apply_admissions(states_in, admissions)
            spec_rec = self._launch(states_in, plan, rec.chunk_idx + 1,
                                    self._next_chunk_len())
        clean = self._commit(rec)
        self._maybe_priority_preempt()
        admitted = self._refill()
        if (clean and spec_rec is not None
                and [(i, s.sid) for i, s in admitted]
                == [(i, s.sid) for i, s in spec[1]]):
            self._pending = spec_rec
        elif spec_rec is not None:
            # the commit deviated from the speculation: drop the launched
            # futures unobserved and relaunch from committed state next step
            self._record('squash', chunk=spec_rec.chunk_idx)
        return True

    def step(self) -> bool:
        """Advance every active stream by up to one chunk of frames.

        Admits pending streams into free slots (priority first), packs all
        active streams into ONE batched chunked call (padded slots masked
        out via ``valid_len``), scatters the valid output rows back to the
        sessions, and retires exhausted streams.  Synchronous mode launches
        and immediately commits (a depth-0 pipeline); ``async_dispatch``
        defers the commit one step so host packing overlaps device compute
        (``_step_async``).  With a fault config attached the commit is
        driven by the generalized ``FaultTolerantRunner`` (injected
        failures degrade the backend and retry the SAME chunk
        synchronously; overruns of the per-chunk deadline are recorded
        against launch-to-commit time), the packed cache is scrubbed by the
        non-finite quarantine before commit, and nothing — states, cursors,
        outputs — is committed unless the attempt succeeded, so a retried
        chunk is recomputed from unchanged state.  Returns False when there
        was nothing to do (the drain-loop exit condition).

        The step runs in a ``serving.step`` span, its phases in
        ``serving.admit``, ``.pack``, ``.dispatch``, ``.wait``, ``.fetch``
        and ``.deliver`` spans (``serving/spans.py``).
        """
        with span('step'):
            self._poll_recovery()
            if self.async_dispatch:
                return self._step_async()
            self._maybe_priority_preempt()
            self._refill()
            plan = [(i, s, s.cursor) for i, s in self.sched.active()]
            if not plan:
                return False
            rec = self._launch(self.states, plan, self._step_idx,
                               self._next_chunk_len())
            self._commit(rec)
            return True

    def run(self) -> List[StreamSession]:
        """Drain: step until every submitted stream has been served (the
        async pipeline is fully committed on exit)."""
        while self.sched.busy:
            self.step()
        self._sync()
        return self.sched.done

    # ------------------------------------------------------------- metrics
    def stats(self) -> dict:
        """Throughput/latency summary over the completed streams, plus the
        §10 fault telemetry: merged structured events (engine + runner),
        per-kind counts, deadline-miss total, the current (possibly
        degraded) backend, and the runner's last heartbeat.  §11 additions:
        the dispatch mode and the chunk-size policy's current size/miss
        count.  ``p50_chunk_s`` is the median launch-to-commit wall time of
        the recent chunks (the last ``CHUNK_WALLS_KEPT``).  Read-only
        snapshot — an async in-flight chunk is NOT committed by this
        call."""
        done = self.sched.done
        frames = sum(s.length for s in done)
        lats = [s.t_done - s.t_enqueue for s in done if s.t_done]
        events = list(self.events)
        if self._runner is not None:
            events += self._runner.events
        counts: dict = {}
        for e in events:
            counts[e['kind']] = counts.get(e['kind'], 0) + 1
        if self._runner is not None:
            misses = self._runner.deadline_misses
        elif self._policy is not None:
            misses = self._policy.misses
        else:
            misses = 0
        dropped = self.events.dropped
        if self._runner is not None:
            dropped += self._runner.events.dropped
        return {
            'streams': len(done),
            'frames': frames,
            'p50_latency_s': float(np.median(lats)) if lats else 0.0,
            'p50_chunk_s': (float(np.median(self.chunk_walls))
                            if self.chunk_walls else 0.0),
            'backend': self.backend,
            'steps': self._step_idx,
            'async': self.async_dispatch,
            'chunk_len': self._next_chunk_len(),
            'events': events,
            'event_counts': counts,
            'events_dropped': dropped,
            'deadline_misses': misses,
            'rung': (self._rungs[self._rung_idx].label()
                     if self._rungs else self.backend),
            'healthy_domains': (list(self._tracker.healthy)
                                if self._tracker else None),
            'heartbeat': (self._runner.last_heartbeat
                          if self._runner else None),
        }
