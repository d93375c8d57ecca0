"""Canonical peephole LSTM — the workload Chipmunk executes (paper Eqs. 1-5).

    i_t = sigma(W_xi x_t + W_hi h_{t-1} + w_ci . c_{t-1} + b_i)
    f_t = sigma(W_xf x_t + W_hf h_{t-1} + w_cf . c_{t-1} + b_f)
    c_t = f_t . c_{t-1} + i_t . tanh(W_xc x_t + W_hc h_{t-1} + b_c)
    o_t = sigma(W_xo x_t + W_ho h_{t-1} + w_co . c_t + b_o)
    h_t = o_t . tanh(c_t)

The peephole matrices are diagonal by construction (footnote 1 of the paper), so they
are stored as vectors and applied element-wise — exactly what the silicon implements.

Gate storage order throughout the package: (i, f, g, o) where g is the cell candidate.
Weights are packed as W[4, N_h, N_in] so the systolic tiler can block them uniformly.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

GATES = 4  # i, f, g, o
I, F, G, O = 0, 1, 2, 3
PEEP_I, PEEP_F, PEEP_O = 0, 1, 2


class LSTMParams(NamedTuple):
    w_x: jax.Array    # (4, N_h, N_x)
    w_h: jax.Array    # (4, N_h, N_h)
    w_peep: jax.Array  # (3, N_h)   diagonal peepholes for i, f, o
    b: jax.Array      # (4, N_h)

    @property
    def n_h(self) -> int:
        return self.w_h.shape[-1]

    @property
    def n_x(self) -> int:
        return self.w_x.shape[-1]

    def num_params(self) -> int:
        return sum(int(jnp.size(p)) for p in self)


def init_lstm_params(key: jax.Array, n_x: int, n_h: int,
                     dtype=jnp.float32, forget_bias: float = 1.0) -> LSTMParams:
    kx, kh, kp = jax.random.split(key, 3)
    sx = 1.0 / jnp.sqrt(n_x)
    sh = 1.0 / jnp.sqrt(n_h)
    b = jnp.zeros((GATES, n_h), dtype)
    b = b.at[F].set(forget_bias)  # standard LSTM trick; keeps early training stable
    return LSTMParams(
        w_x=(jax.random.uniform(kx, (GATES, n_h, n_x), dtype, -1, 1) * sx),
        w_h=(jax.random.uniform(kh, (GATES, n_h, n_h), dtype, -1, 1) * sh),
        w_peep=(jax.random.uniform(kp, (3, n_h), dtype, -1, 1) * 0.1),
        b=b,
    )


def lstm_cell(params: LSTMParams, x_t: jax.Array, h_prev: jax.Array,
              c_prev: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One LSTM timestep.  x_t: (..., N_x); h_prev, c_prev: (..., N_h)."""
    # (..., 4, N_h) pre-activations; the matrix-vector products of Fig. 1 (green).
    pre = (jnp.einsum('ghx,...x->...gh', params.w_x, x_t)
           + jnp.einsum('ghk,...k->...gh', params.w_h, h_prev))
    i = jax.nn.sigmoid(pre[..., I, :] + params.w_peep[PEEP_I] * c_prev + params.b[I])
    f = jax.nn.sigmoid(pre[..., F, :] + params.w_peep[PEEP_F] * c_prev + params.b[F])
    g = jnp.tanh(pre[..., G, :] + params.b[G])
    c_t = f * c_prev + i * g
    o = jax.nn.sigmoid(pre[..., O, :] + params.w_peep[PEEP_O] * c_t + params.b[O])
    h_t = o * jnp.tanh(c_t)
    return h_t, c_t


def lstm_layer(params: LSTMParams, xs: jax.Array,
               h0: Optional[jax.Array] = None,
               c0: Optional[jax.Array] = None) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Scan a layer over time.  xs: (T, ..., N_x) -> hs: (T, ..., N_h).

    The input-state contribution W_x @ x_t is hoisted out of the scan as one
    (T*B)-wide matmul — the sequential loop only carries the recurrent
    W_h @ h_{t-1} part.  Besides halving in-loop matmuls, this moves the
    dW_x reduction out of the time loop (one all-reduce instead of T under
    data parallelism).  The silicon streams x the same way (Sec. 3.2).
    """
    n_h = params.n_h
    batch_shape = xs.shape[1:-1]
    if h0 is None:
        h0 = jnp.zeros(batch_shape + (n_h,), xs.dtype)
    if c0 is None:
        c0 = jnp.zeros(batch_shape + (n_h,), xs.dtype)

    pre_x = jnp.einsum('ghx,t...x->t...gh', params.w_x, xs)   # hoisted

    def step(carry, pre_x_t):
        h, c = carry
        pre = pre_x_t + jnp.einsum('ghk,...k->...gh', params.w_h, h)
        i = jax.nn.sigmoid(pre[..., I, :] + params.w_peep[PEEP_I] * c + params.b[I])
        f = jax.nn.sigmoid(pre[..., F, :] + params.w_peep[PEEP_F] * c + params.b[F])
        g = jnp.tanh(pre[..., G, :] + params.b[G])
        c = f * c + i * g
        o = jax.nn.sigmoid(pre[..., O, :] + params.w_peep[PEEP_O] * c + params.b[O])
        h = o * jnp.tanh(c)
        return (h, c), h

    (h_T, c_T), hs = jax.lax.scan(step, (h0, c0), pre_x)
    return hs, (h_T, c_T)


# ---------------------------------------------------------------------------
# Hand-written layer VJP: weight gradients accumulate OUTSIDE the time loop
# (autodiff-of-scan reduces dW across data shards every step — measured
# 62 GB/chip/step on the chipmunk-ctc train cell; this does it once).
# ---------------------------------------------------------------------------

def _cell_body(w_h, w_peep, b, pre_x_t, h, c_prev):
    """Shared gate math of the scan-family step functions (`_lstm_scan` and
    the masked serving variant), so the two cannot silently diverge.  The
    spelled-out forms in ``lstm_cell``/``lstm_layer`` stay independent — they
    are the paper-equation oracles both scan paths are tested against.
    Returns (h_new, c_new, (i, f, g, o))."""
    pre = pre_x_t + jnp.einsum('ghk,...k->...gh', w_h, h)
    i = jax.nn.sigmoid(pre[..., I, :] + w_peep[PEEP_I] * c_prev + b[I])
    f = jax.nn.sigmoid(pre[..., F, :] + w_peep[PEEP_F] * c_prev + b[F])
    g = jnp.tanh(pre[..., G, :] + b[G])
    c = f * c_prev + i * g
    o = jax.nn.sigmoid(pre[..., O, :] + w_peep[PEEP_O] * c + b[O])
    h_new = o * jnp.tanh(c)
    return h_new, c, (i, f, g, o)


def _lstm_scan(w_h, w_peep, b, pre_x, h0, c0):
    def step(carry, pre_x_t):
        h, c_prev = carry
        h_new, c, (i, f, g, o) = _cell_body(w_h, w_peep, b, pre_x_t, h, c_prev)
        gates = jnp.stack([i, f, g, o], axis=-2)
        return (h_new, c), (h_new, c, gates)

    (h_T, c_T), (hs, cs, gates) = jax.lax.scan(step, (h0, c0), pre_x)
    return hs, cs, gates, h_T, c_T


@jax.custom_vjp
def lstm_scan_fused(w_h, w_peep, b, pre_x, h0, c0):
    hs, _, _, h_T, c_T = _lstm_scan(w_h, w_peep, b, pre_x, h0, c0)
    return hs, (h_T, c_T)


def _lsf_fwd(w_h, w_peep, b, pre_x, h0, c0):
    hs, cs, gates, h_T, c_T = _lstm_scan(w_h, w_peep, b, pre_x, h0, c0)
    return (hs, (h_T, c_T)), (w_h, w_peep, hs, cs, gates, h0, c0)


def lstm_bwd_core(w_h, w_peep, hs, cs, gates, h0, c0, dhs, dh_T, dc_T):
    """Shared reverse-time scan: used by the scan VJP and the Pallas-sequence
    kernel VJP (which recomputes ``gates`` instead of storing them)."""
    h_prevs = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    c_prevs = jnp.concatenate([c0[None], cs[:-1]], axis=0)

    def step(carry, xs):
        dh_next, dc_next = carry
        dh_out, c_prev, c_t, gate_t = xs
        i, f, g, o = (gate_t[..., k, :] for k in range(4))
        dh = dh_out + dh_next
        tc = jnp.tanh(c_t)
        do = dh * tc
        da_o = do * o * (1 - o)
        dct = dh * o * (1 - tc * tc) + dc_next + da_o * w_peep[PEEP_O]
        da_i = dct * g * i * (1 - i)
        da_f = dct * c_prev * f * (1 - f)
        da_g = dct * i * (1 - g * g)
        dc_prev = dct * f + da_i * w_peep[PEEP_I] + da_f * w_peep[PEEP_F]
        da = jnp.stack([da_i, da_f, da_g, da_o], axis=-2)   # (..., 4, Nh)
        dh_prev = jnp.einsum('ghk,...gh->...k', w_h, da)
        return (dh_prev, dc_prev), da

    (dh0, dc0), das = jax.lax.scan(
        step, (dh_T, dc_T), (dhs, c_prevs, cs, gates), reverse=True)

    # weight gradients: single wide contractions outside the loop
    dw_h = jnp.einsum('t...gh,t...k->ghk', das, h_prevs)
    d_peep = jnp.stack([
        jnp.einsum('t...h,t...h->h', das[..., I, :], c_prevs),
        jnp.einsum('t...h,t...h->h', das[..., F, :], c_prevs),
        jnp.einsum('t...h,t...h->h', das[..., O, :], cs)])
    db = das.sum(axis=tuple(range(das.ndim - 2)))
    dpre_x = das
    return dw_h, d_peep, db, dpre_x, dh0, dc0


def _lsf_bwd(res, grads):
    w_h, w_peep, hs, cs, gates, h0, c0 = res
    dhs, (dh_T, dc_T) = grads
    return lstm_bwd_core(w_h, w_peep, hs, cs, gates, h0, c0, dhs, dh_T, dc_T)


lstm_scan_fused.defvjp(_lsf_fwd, _lsf_bwd)


def lstm_bwd_recompute_gates(w_h, w_peep, b, pre_x, hs, cs, h0, c0, grads):
    """Backward from the saved h/c trajectories only (no stored gates).

    The Pallas kernels keep gate values on-chip, so their VJPs recompute them
    with one wide matmul + elementwise — the same trade the scan VJP makes
    for dW accumulation — then run the shared reverse-time scan.  Returns
    (dw_h, d_peep, db, dpre_x, dh0, dc0).
    """
    dhs, (dh_T, dc_T) = grads
    h_prevs = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    c_prevs = jnp.concatenate([c0[None], cs[:-1]], axis=0)
    pre = pre_x + jnp.einsum('ghk,t...k->t...gh', w_h, h_prevs)
    i = jax.nn.sigmoid(pre[..., I, :] + w_peep[PEEP_I] * c_prevs + b[I])
    f = jax.nn.sigmoid(pre[..., F, :] + w_peep[PEEP_F] * c_prevs + b[F])
    g = jnp.tanh(pre[..., G, :] + b[G])
    o = jax.nn.sigmoid(pre[..., O, :] + w_peep[PEEP_O] * cs + b[O])
    gates = jnp.stack([i, f, g, o], axis=-2)
    return lstm_bwd_core(w_h, w_peep, hs, cs, gates, h0, c0, dhs, dh_T, dc_T)


def lstm_stack_bwd_recompute_gates(w_in, w_h, peep, b, pre_x, hs, cs, h0s,
                                   c0s, grads):
    """Cross-layer gate-recompute backward for a homogeneous LSTM stack.

    Composes ``lstm_bwd_recompute_gates`` down the stack from the saved
    per-layer h/c trajectories: each layer's hoisted input stream is
    recomputed from the trajectory below it (layer 0's ``pre_x`` was a
    primal input), the inner layers' input-weight gradients and the
    handover cotangents being the only additions over the single-layer
    VJP.  Shared by the fused wavefront kernel's VJP
    (``kernels.lstm_seq.stack_ops``) and the staged systolic scale-out's
    VJP (``core.systolic.systolic_lstm_stack_seq``), so the two backward
    paths cannot diverge.

    w_in/w_h: (L, 4, N_h, N_h) with ``w_in[0]`` zero; pre_x: (T, B, 4,
    N_h); hs/cs: (L, T, B, N_h) saved trajectories; h0s/c0s: (L, B, N_h);
    grads: (d_ys, (d_hT (L, B, N_h), d_cT)).  Returns (dw_in, dw_h,
    d_peep, db, d_pre_x0, dh0s, dc0s).
    """
    d_ys, (d_hT, d_cT) = grads
    L = w_h.shape[0]
    dw_in, dw_h, d_peep, db, dh0, dc0 = [], [], [], [], [], []
    d_hs = d_ys                     # cotangent flowing into the top layer
    d_pre_x0 = None
    for l in range(L - 1, -1, -1):
        pre_l = pre_x if l == 0 else jnp.einsum('ghx,tbx->tbgh',
                                                w_in[l], hs[l - 1])
        dwh, dp, dbias, dpre, dh, dc = lstm_bwd_recompute_gates(
            w_h[l], peep[l], b[l], pre_l, hs[l], cs[l], h0s[l], c0s[l],
            (d_hs, (d_hT[l], d_cT[l])))
        dw_h.append(dwh)
        d_peep.append(dp)
        db.append(dbias)
        dh0.append(dh)
        dc0.append(dc)
        if l > 0:
            dw_in.append(jnp.einsum('tbgh,tbx->ghx', dpre, hs[l - 1]))
            d_hs = jnp.einsum('ghx,tbgh->tbx', w_in[l], dpre)
        else:
            dw_in.append(jnp.zeros_like(w_in[0]))
            d_pre_x0 = dpre
    stack = lambda xs: jnp.stack(xs[::-1])
    return (stack(dw_in), stack(dw_h), stack(d_peep), stack(db),
            d_pre_x0, stack(dh0), stack(dc0))


# ---------------------------------------------------------------------------
# Backend dispatch: xla_scan | pallas_step | pallas_seq | pallas_seq_fused |
# pallas_seq_systolic | pallas_seq_fused_systolic (DESIGN.md §3.3, §6, §8, §9)
# ---------------------------------------------------------------------------

BACKENDS = ('auto', 'xla_scan', 'pallas_step', 'pallas_seq',
            'pallas_seq_fused', 'pallas_seq_systolic',
            'pallas_seq_fused_systolic')

# Serving degradation ladder (DESIGN.md §10): when a mesh engine is declared
# dead mid-serve, the fault-tolerant serving runtime re-dispatches to the
# next backend DOWN this ladder — from the full staged scale-out through the
# single-host fused stack and the per-layer sequence kernel to the
# always-available XLA scan.  Backends not named on the ladder map onto the
# nearest rung (``_LADDER_RANK``): the layerwise systolic scale-out degrades
# like the staged one (both die with the mesh), the per-step kernel like the
# sequence kernel.
DEGRADATION_LADDER = ('pallas_seq_fused_systolic', 'pallas_seq_fused',
                      'pallas_seq', 'xla_scan')
_LADDER_RANK = {'pallas_seq_fused_systolic': 0, 'pallas_seq_systolic': 0,
                'pallas_seq_fused': 1, 'pallas_seq': 2, 'pallas_step': 2,
                'xla_scan': 3}


def next_backend_down(backend: str) -> Optional[str]:
    """The next backend down the serving ``DEGRADATION_LADDER``, or None at
    the bottom (``xla_scan`` has no fallback — a fault there is retried,
    not degraded).  Pure dispatch — selection never changes the chunking /
    masking contract, only which engine executes it; a degraded backend's
    outputs agree with the original to float tolerance (allclose), and
    bit-equality contracts continue to hold per backend code path."""
    rank = _LADDER_RANK.get(backend)
    if rank is None or rank + 1 >= len(DEGRADATION_LADDER):
        return None
    return DEGRADATION_LADDER[rank + 1]


def next_backend_up(backend: str) -> Optional[str]:
    """The next backend UP the serving ``DEGRADATION_LADDER``, or None at
    the top (the staged systolic rung has nothing above it) — the promotion
    inverse of ``next_backend_down``, consulted by the recovery runtime
    (``runtime/recovery.py``) when the mesh health tracker reports capacity
    for a higher rung.  Pure dispatch — promotion is canary-validated by
    the engine before it takes effect, and never changes the chunking /
    masking contract, only which engine executes it."""
    rank = _LADDER_RANK.get(backend)
    if rank is None or rank == 0:
        return None
    return DEGRADATION_LADDER[rank - 1]

# The sequence kernel keeps W_h + state resident in VMEM; leave headroom for
# Mosaic's double-buffered streams out of the ~16 MB budget.
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_SEQ_MIN_T = 8  # below this, per-launch savings don't pay for residency setup


def select_lstm_backend(n_x: int, n_h: int, T: int, batch: int,
                        *, platform: Optional[str] = None,
                        mesh=None) -> str:
    """Shape-based backend selection (see DESIGN.md §3.3 and §6).

    When a systolic mesh is installed (``core.systolic.install_mesh`` /
    ``launch/mesh.py`` presets) and it admits the layer, ``auto`` resolves to
    the multi-engine scale-out backend ``pallas_seq_systolic`` on ANY
    platform — shard_map is real SPMD, not interpret-mode emulation, so it is
    meaningful on CPU host devices too.  Otherwise, on non-TPU platforms
    Pallas kernels only exist in interpret mode (an emulation for validation,
    not speed), so ``auto`` resolves to the XLA scan there; tests and
    benchmarks opt into the kernels explicitly.
    """
    if mesh is None:
        from .systolic import current_mesh
        mesh = current_mesh()
    if mesh is not None and T >= _SEQ_MIN_T:
        from .systolic import seq_scaleout_admissible
        if seq_scaleout_admissible(n_h, mesh):
            return 'pallas_seq_systolic'
    platform = platform or jax.default_backend()
    if platform != 'tpu':
        return 'xla_scan'
    from ..kernels.lstm_seq import vmem_bytes_estimate
    if T >= _SEQ_MIN_T and vmem_bytes_estimate(n_h, batch) <= _VMEM_BUDGET_BYTES:
        return 'pallas_seq'
    if n_h * (n_x + n_h) * 4 * GATES <= _VMEM_BUDGET_BYTES:
        return 'pallas_step'
    return 'xla_scan'


def _stack_backend_admissible(backend: str, n_x: int, n_h: int,
                              n_layers: int, T: int, batch: int, *,
                              platform: Optional[str] = None,
                              mesh=None) -> bool:
    """Whether a cached stack-backend winner may be honoured HERE.

    The schedule cache records measured winners, but admission stays with
    the live rules: the systolic backends need the (admissible) mesh they
    were measured on, and the raw Pallas kernels only exist as interpret-
    mode emulation off-TPU — a cache must never be able to force either.
    ``xla_scan`` is admissible everywhere.
    """
    if backend not in BACKENDS or backend == 'auto':
        return False
    if backend == 'xla_scan':
        return True
    if backend in ('pallas_seq_systolic', 'pallas_seq_fused_systolic'):
        from .systolic import seq_scaleout_admissible
        layers = n_layers if backend == 'pallas_seq_fused_systolic' else None
        return (mesh is not None and T >= _SEQ_MIN_T
                and seq_scaleout_admissible(n_h, mesh, n_layers=layers,
                                            n_x=n_x, T=T, batch=batch))
    return (platform or jax.default_backend()) == 'tpu'


def select_stack_backend(n_x: int, n_h: int, n_layers: int, T: int,
                         batch: int, *, platform: Optional[str] = None,
                         mesh=None) -> str:
    """Stack-level backend selection (DESIGN.md §8 and §9).

    The fused wavefront kernel is a STACK-level choice: it is admitted only
    when the whole stack's resident working set — every layer's recurrent
    AND input weight blocks (``stack_vmem_bytes_estimate``) — fits the VMEM
    budget, there are at least two layers to pipeline, and the sequence is
    long enough to amortise residency.  An installed systolic mesh takes
    precedence (the user asked for multi-engine scale-out): a mesh with a
    live ``stage`` axis that admits the stack (the stage-aware form of
    ``seq_scaleout_admissible``) resolves to the staged scale-out of the
    fused stack, ``pallas_seq_fused_systolic`` (§9 — the paper's 3×(5×5)
    Table-2 topology as one dispatch path); a stage-1 mesh resolves to the
    layerwise ``pallas_seq_systolic``.  Everything else falls back to the
    per-layer ``select_lstm_backend`` rules, i.e. the layerwise
    composition.  Selection never changes numerics — all backends are
    interchangeable.

    An installed schedule cache (``repro.tune``, kind ``'stack_backend'``)
    takes precedence over every heuristic below — a measured winner beats
    an estimated one — but only when the named backend is still admissible
    here (mesh present/admissible for the systolic backends, TPU for the
    raw Pallas kernels): admission guards are correctness/efficiency
    gates, not preferences, so a stale cache can never force an
    inadmissible launch.
    """
    if mesh is None:
        from .systolic import current_mesh
        mesh = current_mesh()
    tuned = _tuned_backend('stack_backend', n_x, n_h, n_layers, T, batch,
                           mesh=mesh)
    if tuned is not None and _stack_backend_admissible(
            tuned, n_x, n_h, n_layers, T, batch, platform=platform,
            mesh=mesh):
        return tuned
    if mesh is not None and T >= _SEQ_MIN_T:
        from .systolic import seq_scaleout_admissible
        if seq_scaleout_admissible(n_h, mesh, n_layers=n_layers,
                                   n_x=n_x, T=T, batch=batch):
            return 'pallas_seq_fused_systolic'
    per_layer = select_lstm_backend(n_x, n_h, T, batch,
                                    platform=platform, mesh=mesh)
    if per_layer == 'pallas_seq_systolic':
        return per_layer
    platform = platform or jax.default_backend()
    if platform != 'tpu':
        return per_layer
    from ..kernels.lstm_seq import stack_vmem_bytes_estimate
    if (n_layers >= 2 and T >= _SEQ_MIN_T
            and stack_vmem_bytes_estimate(n_x, n_h, n_layers, batch)
            <= _VMEM_BUDGET_BYTES):
        return 'pallas_seq_fused'
    return per_layer


# Cold-cache fallback for the int8 stack dispatch (BENCH_kernels.json pair
# "T=32 B=4 48->96x3 tile=48 int8"): the fused wavefront LOSES to the
# layerwise chain at 96 hidden (23.9 ms vs 14.0 ms) — its L-1-diagonal
# fill/drain bubble, stacked-weight relayout, and diagonal re-indexing are
# fixed costs, while the per-layer matmul work it amortises shrinks with the
# hidden width.  Without a measured schedule-cache entry (``repro.tune``),
# fused admission therefore requires a hidden width safely above that
# measured losing point; the paper's 421-hidden Table-2 stack clears it.
_Q_FUSED_MIN_NH = 256


def _tuned_backend(kind: str, n_x: int, n_h: int, n_layers: int, T: int,
                   batch: int, mesh=None) -> Optional[str]:
    """Measured winner for a backend decision from the installed schedule
    cache (``repro.tune.install_schedule_cache``), or None on a miss.
    Dispatch-only by the cache contract: every backend an entry can name is
    numerics-equivalent to the fallback choice, so a hit changes the launch
    shape, never the outputs."""
    from ..tune.schedule import current_schedule_cache, mesh_signature
    cache = current_schedule_cache()
    if cache is None:
        return None
    ent = cache.lookup(kind, n_x=n_x, n_h=n_h, n_layers=n_layers, T=T,
                       B=batch, mesh=mesh_signature(mesh))
    return ent.backend if ent is not None and ent.backend else None


def select_quantized_stack_backend(n_h: int, n_layers: int, T: int,
                                   batch: int) -> str:
    """Int8 stack dispatch: ``'fused'`` (the §8 wavefront
    ``lstm_stack_seq_quantized``) or ``'layerwise'`` (chained
    ``lstm_layer_seq_quantized``).  Both are bit-identical — this picks the
    faster launch shape only.  The structural guards are authoritative (the
    wavefront needs at least two layers to pipeline and a sequence long
    enough to amortise residency, ``_SEQ_MIN_T``); past them, a MEASURED
    winner from the installed schedule cache (``repro.tune``, kind
    ``'q_stack_backend'``) decides, and only on a cache miss does the
    hand-calibrated ``_Q_FUSED_MIN_NH`` hidden-width floor — below it the
    measured BENCH_kernels.json rows show the layerwise chain winning —
    remain as the cold-cache fallback."""
    if n_layers < 2 or T < _SEQ_MIN_T:
        return 'layerwise'
    tuned = _tuned_backend('q_stack_backend', n_h, n_h, n_layers, T, batch)
    if tuned in ('fused', 'layerwise'):
        return tuned
    return 'fused' if n_h >= _Q_FUSED_MIN_NH else 'layerwise'


def _degrade_staged_single_layer(n_h: int) -> str:
    """A single-layer call cannot stage-pipeline (nothing to place on the
    stage axis): ``pallas_seq_fused_systolic`` degrades to the layerwise
    scale-out when the installed mesh admits the layer on its row/col axes
    alone, and to the single-engine sequence kernel otherwise.  Pure
    dispatch — no numerics of its own."""
    from .systolic import current_mesh, seq_scaleout_admissible
    return ('pallas_seq_systolic'
            if seq_scaleout_admissible(n_h, current_mesh())
            else 'pallas_seq')


def lstm_layer_fused(params: LSTMParams, xs: jax.Array,
                     h0: Optional[jax.Array] = None,
                     c0: Optional[jax.Array] = None, *,
                     backend: str = 'auto'):
    """lstm_layer with the hand-written VJP (production training path).

    ``backend`` selects the execution engine: the XLA scan, the per-timestep
    Pallas kernel, the persistent whole-sequence Pallas kernel, or the
    multi-engine systolic scale-out of the sequence kernel (which reads the
    installed mesh — ``core.systolic.install_mesh``); ``auto`` picks by
    shape/platform/mesh (select_lstm_backend).  All backends are numerically
    interchangeable: forward allclose, backward through the same
    gate-recompute VJP family.
    """
    assert backend in BACKENDS, backend
    n_h = params.n_h
    batch_shape = xs.shape[1:-1]
    if backend == 'auto':
        backend = select_lstm_backend(params.n_x, n_h, xs.shape[0],
                                      math.prod(batch_shape))
    if backend == 'pallas_seq_fused':
        backend = 'pallas_seq'      # a 1-layer stack IS the sequence kernel
    if backend == 'pallas_seq_fused_systolic':
        backend = _degrade_staged_single_layer(n_h)
    if h0 is None:
        h0 = jnp.zeros(batch_shape + (n_h,), xs.dtype)
    if c0 is None:
        c0 = jnp.zeros(batch_shape + (n_h,), xs.dtype)
    if backend == 'pallas_seq_systolic':
        from .systolic import current_mesh, systolic_lstm_seq
        T = xs.shape[0]
        flat_b = math.prod(batch_shape)
        hs, (h_T, c_T) = systolic_lstm_seq(
            params, current_mesh(), xs.reshape(T, flat_b, params.n_x),
            h0.reshape(flat_b, n_h), c0.reshape(flat_b, n_h))
        return (hs.reshape((T,) + batch_shape + (n_h,)),
                (h_T.reshape(batch_shape + (n_h,)),
                 c_T.reshape(batch_shape + (n_h,))))
    if backend == 'pallas_seq':
        from ..kernels.lstm_seq import lstm_layer_seq
        return lstm_layer_seq(params, xs, h0, c0)
    if backend == 'pallas_step':
        from ..kernels.lstm_gates import lstm_layer_fused as step_layer
        T = xs.shape[0]
        flat_b = math.prod(batch_shape)
        hs, (h_T, c_T) = step_layer(
            params, xs.reshape(T, flat_b, params.n_x),
            h0=h0.reshape(flat_b, n_h), c0=c0.reshape(flat_b, n_h),
            return_state=True)
        return (hs.reshape((T,) + batch_shape + (n_h,)),
                (h_T.reshape(batch_shape + (n_h,)),
                 c_T.reshape(batch_shape + (n_h,))))
    pre_x = jnp.einsum('ghx,t...x->t...gh', params.w_x, xs)
    return lstm_scan_fused(params.w_h, params.w_peep, params.b, pre_x, h0, c0)


# ---------------------------------------------------------------------------
# Chunked stateful serving entry points (DESIGN.md §7)
# ---------------------------------------------------------------------------

def valid_len_mask(T: int, valid_len: jax.Array, batch: int) -> jax.Array:
    """The §7 masking contract in one place: step ``t`` of stream ``b`` is
    live iff ``t < valid_len[b]``.  Returns a bool (T, B) mask — every
    masked backend (scan, Pallas kernels, distributed body) derives its
    mask from this single definition so the contract cannot silently
    diverge between them."""
    return (jnp.arange(T, dtype=jnp.int32)[:, None]
            < valid_len.reshape(batch).astype(jnp.int32)[None, :])


def _lstm_scan_masked(w_h, w_peep, b, pre_x, h0, c0, mask):
    """Masked scan: a masked step is identity on (h, c) and re-emits the
    carried ``h`` — the reference semantics every masked backend matches.
    The gate math is the shared ``_cell_body`` (same as ``_lstm_scan``)."""
    def step(carry, inp):
        h, c = carry
        pre_x_t, m = inp
        h_new, c_new, _ = _cell_body(w_h, w_peep, b, pre_x_t, h, c)
        m = m[..., None]
        h = jnp.where(m, h_new, h)
        c = jnp.where(m, c_new, c)
        return (h, c), h

    (h_T, c_T), hs = jax.lax.scan(step, (h0, c0), (pre_x, mask))
    return hs, (h_T, c_T)


def lstm_layer_chunk(params: LSTMParams, xs: jax.Array,
                     h0: Optional[jax.Array] = None,
                     c0: Optional[jax.Array] = None, *,
                     valid_len: Optional[jax.Array] = None,
                     backend: str = 'auto'
                     ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Stateful chunked layer step — the serving-engine primitive (§7).

    Same contract as ``lstm_layer`` / ``lstm_layer_fused`` on the live steps,
    plus ragged masking: ``valid_len`` (B,) marks steps ``t >= valid_len[b]``
    as identity on the state (the carried ``h`` is re-emitted), so the
    returned ``(h_T, c_T)`` is the state after exactly ``valid_len[b]`` steps
    and feeding a sequence chunk by chunk is bit-equal to one monolithic
    call on the same backend.  xs: (T, B, N_x).  With ``valid_len=None``
    this is exactly ``lstm_layer_fused`` (differentiable); the masked path
    is inference-only.  ``pallas_step`` has no masked form — masked chunks
    fall back to the (allclose) masked XLA scan.
    """
    if valid_len is None:
        return lstm_layer_fused(params, xs, h0, c0, backend=backend)
    assert backend in BACKENDS, backend
    assert xs.ndim == 3, 'lstm_layer_chunk expects (T, B, N_x) input'
    T, B = xs.shape[0], xs.shape[1]
    n_h = params.n_h
    if backend == 'auto':
        backend = select_lstm_backend(params.n_x, n_h, T, B)
    if backend == 'pallas_seq_fused':
        backend = 'pallas_seq'      # a 1-layer stack IS the sequence kernel
    if backend == 'pallas_seq_fused_systolic':
        backend = _degrade_staged_single_layer(n_h)
    if h0 is None:
        h0 = jnp.zeros((B, n_h), xs.dtype)
    if c0 is None:
        c0 = jnp.zeros((B, n_h), xs.dtype)
    if backend == 'pallas_seq':
        from ..kernels.lstm_seq import lstm_layer_seq
        return lstm_layer_seq(params, xs, h0, c0, valid_len=valid_len)
    if backend == 'pallas_seq_systolic':
        from .systolic import current_mesh, systolic_lstm_seq
        return systolic_lstm_seq(params, current_mesh(), xs, h0, c0,
                                 valid_len=valid_len)
    # xla_scan — and the masked fallback for pallas_step (no masked kernel).
    mask = valid_len_mask(T, valid_len, B)
    pre_x = jnp.einsum('ghx,tbx->tbgh', params.w_x, xs)
    return _lstm_scan_masked(params.w_h, params.w_peep, params.b, pre_x,
                             h0, c0, mask)


class LSTMStackParams(NamedTuple):
    layers: Tuple[LSTMParams, ...]
    w_out: Optional[jax.Array]  # (N_out, N_h) final dense layer (paper: y = sigma(W_hy h))
    b_out: Optional[jax.Array]

    def num_params(self) -> int:
        n = sum(l.num_params() for l in self.layers)
        if self.w_out is not None:
            n += int(jnp.size(self.w_out)) + int(jnp.size(self.b_out))
        return n


def init_lstm_stack(key: jax.Array, n_x: int, n_h: int, n_layers: int,
                    n_out: Optional[int] = None, dtype=jnp.float32) -> LSTMStackParams:
    keys = jax.random.split(key, n_layers + 1)
    layers = []
    for l in range(n_layers):
        layers.append(init_lstm_params(keys[l], n_x if l == 0 else n_h, n_h, dtype))
    w_out = b_out = None
    if n_out is not None:
        w_out = jax.random.uniform(keys[-1], (n_out, n_h), dtype, -1, 1) / jnp.sqrt(n_h)
        b_out = jnp.zeros((n_out,), dtype)
    return LSTMStackParams(tuple(layers), w_out, b_out)


def stack_carry_arrays(states, n_layers: int, batch: int, n_h: int,
                       dtype) -> Tuple[jax.Array, jax.Array]:
    """Stack per-layer serving carries into the (L, B, N_h) kernel arrays.

    The ONE defaulting rule for fused-stack entry points (the §8 kernel
    wrapper and the §9 staged scale-out): a missing state list, a missing
    layer entry, or a ``None`` half zeroes THAT layer's carry only, never
    its neighbours' — exactly what the layerwise loop does, so backends
    stay numerically interchangeable.  Returns (h0s, c0s).
    """
    zeros = jnp.zeros((batch, n_h), dtype)

    def gather(part):
        def one(l):
            st = None if states is None else states[l]
            v = None if st is None else st[part]
            return zeros if v is None else v

        return jnp.stack([one(l) for l in range(n_layers)])

    return gather(0), gather(1)


def _resolve_stack_backend(params: LSTMStackParams, backend: str,
                           xs: jax.Array) -> str:
    """Stack-level dispatch (DESIGN.md §8 and §9): resolve ``auto`` through
    ``select_stack_backend`` and degrade an (explicit or auto-picked)
    fused-stack backend when the stack is structurally incompatible with
    the wavefront schedule (heterogeneous widths, a single layer, or a
    non-(T, B, N_x) input): ``pallas_seq_fused`` falls back to the
    layerwise ``pallas_seq``, the staged ``pallas_seq_fused_systolic``
    likewise (a heterogeneous stack cannot share one stage-padded weight
    layout; the stage>1 installed mesh is not usable layerwise, so the
    single-engine composition decides).  Pure dispatch — the chosen
    backend never changes numerics beyond float re-association."""
    from ..kernels.lstm_seq import stack_fused_compatible
    compatible = (xs.ndim == 3 and len(params.layers) >= 2
                  and stack_fused_compatible(params))
    if backend == 'auto' and compatible:
        l0 = params.layers[0]
        backend = select_stack_backend(l0.n_x, l0.n_h, len(params.layers),
                                       xs.shape[0], xs.shape[1])
    if backend in ('pallas_seq_fused',
                   'pallas_seq_fused_systolic') and not compatible:
        backend = 'pallas_seq'
    return backend


def resolve_serving_backend(params: LSTMStackParams, backend: str,
                            T: int, B: int) -> str:
    """Resolve ``backend`` (incl. ``auto``) to the CONCRETE backend a
    ``(T, B, N_x)`` chunked serving call would dispatch to — the same
    ``_resolve_stack_backend`` selection ``lstm_stack_chunk`` applies, run
    ahead of time on a shape placeholder.  The fault-tolerant serving
    runtime pins this at engine construction so it knows its position on
    the ``DEGRADATION_LADDER`` before any fault occurs.  Pure dispatch —
    resolution never changes numerics.

    Serving chunks are always masked, and ``pallas_step`` has no masked
    form: where it would be picked (a chunk shorter than ``_SEQ_MIN_T`` on
    a TPU, or an explicit request) this resolves to ``xla_scan``, the
    engine that actually runs, so the pinned backend never hides it."""
    l0 = params.layers[0]
    xs = jax.ShapeDtypeStruct((T, B, l0.n_x), jnp.float32)
    resolved = _resolve_stack_backend(params, backend, xs)
    if resolved == 'auto':           # structurally fused-incompatible stack:
        # the per-layer rules decide, exactly as lstm_layer_chunk would
        resolved = select_lstm_backend(l0.n_x, l0.n_h, T, B)
    return 'xla_scan' if resolved == 'pallas_step' else resolved


def lstm_stack_apply(params: LSTMStackParams, xs: jax.Array,
                     states: Optional[Sequence[Tuple[jax.Array, jax.Array]]] = None,
                     backend: str = 'auto') -> Tuple[jax.Array, list]:
    """Full network: stacked LSTM layers + optional dense read-out (logits, no sigma).

    xs: (T, B, N_x).  Returns (ys (T, B, N_out or N_h), final states per layer).

    ``backend='pallas_seq_fused'`` (or ``auto`` when the stack-level rules
    admit it) runs every layer in ONE fused wavefront launch
    (``kernels.lstm_seq.lstm_stack_seq``) instead of the per-layer loop —
    same contract, output allclose, hidden sequences never round-tripping
    through HBM between layers.  ``backend='pallas_seq_fused_systolic'``
    is the staged scale-out of the same composition
    (``core.systolic.systolic_lstm_stack_seq``, DESIGN.md §9): contiguous
    layer blocks pinned to the installed mesh's ``stage`` axis, the fused
    stack running tile-stationary inside each stage.
    """
    assert backend in BACKENDS, backend
    backend = _resolve_stack_backend(params, backend, xs)
    if backend == 'pallas_seq_fused_systolic':
        from .systolic import current_mesh, systolic_lstm_stack_seq
        h, finals = systolic_lstm_stack_seq(params, current_mesh(), xs,
                                            states)
        finals = list(finals)
    elif backend == 'pallas_seq_fused':
        from ..kernels.lstm_seq import lstm_stack_seq
        h, finals = lstm_stack_seq(params, xs, states)
        finals = list(finals)
    else:
        h = xs
        finals = []
        for l, lp in enumerate(params.layers):
            h0c0 = states[l] if states is not None else (None, None)
            h, (h_T, c_T) = lstm_layer_fused(lp, h, *h0c0, backend=backend)
            finals.append((h_T, c_T))
    if params.w_out is not None:
        h = jnp.einsum('oh,tbh->tbo', params.w_out, h) + params.b_out
    return h, finals


def lstm_stack_chunk(params: LSTMStackParams, xs: jax.Array, states,
                     *, valid_len: Optional[jax.Array] = None,
                     backend: str = 'auto') -> Tuple[jax.Array, tuple]:
    """Stateful chunked stack application — ``lstm_stack_apply`` for serving.

    One chunk of ``T`` frames through every layer, composing the per-layer
    ``(h, c)`` carries (the chip's retained internal state).  The same
    ``valid_len`` masks every layer: a masked step re-emits each layer's
    carried ``h``, so the garbage a padded input frame would produce never
    enters any layer's state and chunked output equals the monolithic
    ``lstm_stack_apply`` on the valid prefix (bit-equal on a fixed backend).
    xs: (T, B, N_x); states: per-layer ``((h, c), ...)`` from the previous
    chunk (or zeros).  Returns (ys (T, B, N_out or N_h), new states).

    On the ``pallas_seq_fused`` backend the whole chunk runs every layer in
    one wavefront launch with the per-layer carries and the shared
    ``valid_len`` mask threaded straight into the kernel — the serving
    engine's packed slot grid rides this path end to end.  On
    ``pallas_seq_fused_systolic`` the same chunked call (carries, shared
    mask) runs the staged scale-out over the installed
    (stage, row, col) mesh — the cross-engine state handoff of DESIGN.md
    §9.
    """
    assert backend in BACKENDS, backend
    backend = _resolve_stack_backend(params, backend, xs)
    if backend == 'pallas_seq_fused_systolic':
        from .systolic import current_mesh, systolic_lstm_stack_seq
        h, finals = systolic_lstm_stack_seq(params, current_mesh(), xs,
                                            states, valid_len=valid_len)
        finals = tuple(finals)
    elif backend == 'pallas_seq_fused':
        from ..kernels.lstm_seq import lstm_stack_seq
        h, finals = lstm_stack_seq(params, xs, states, valid_len=valid_len)
        finals = tuple(finals)
    else:
        h = xs
        finals = []
        for l, lp in enumerate(params.layers):
            h0c0 = states[l] if states is not None else (None, None)
            h, (h_T, c_T) = lstm_layer_chunk(lp, h, *h0c0,
                                             valid_len=valid_len,
                                             backend=backend)
            finals.append((h_T, c_T))
        finals = tuple(finals)
    if params.w_out is not None:
        h = jnp.einsum('oh,tbh->tbo', params.w_out, h) + params.b_out
    return h, finals
