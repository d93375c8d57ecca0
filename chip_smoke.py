"""Chip smoke test: the paper's CTC-3L-421H model served on a TPU.

Drives ``chipmunk-ctc`` at its full width (123 MFCC -> 3x421 peephole LSTM
-> 62 CTC outputs, random weights from ``SEED``) through the normal serving
path -- ``StreamServer`` -> ``StreamingEngine`` ->
``chipmunk_net.stream_forward`` with ``lstm_backend='auto'`` -- and checks
what comes out:

  * ``auto`` resolves to a Pallas backend, and the compiled chunk call holds
    a Mosaic kernel (``tpu_custom_call``);
  * every stream's log-probs agree with an independent f32 reference
    (``chipmunk_net.forward`` on the XLA scan at ``highest`` matmul
    precision) within ``LOGPROB_ATOL``;
  * one stream fed chunk by chunk equals one monolithic call on the same
    backend, bit for bit (DESIGN.md §7).

``--four-chips`` runs only the §6 multi-engine scale-out instead: the ``2x2``
systolic preset (``pallas_seq_systolic``) on four chips, compared with the
one-chip ``pallas_seq`` engine on the same inputs.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # 2x2 scale-out vs one chip

The last line of standard output is one JSON object, ``{"ok": true,
"device": {...}}``.  Without a TPU the script exits non-zero and prints no
result.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / 'src'))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import StreamServer  # noqa: E402
from repro.models import chipmunk_net, get_bundle  # noqa: E402

# A v5e runs f32 matmuls outside Pallas kernels (the hoisted W_x @ x stream
# and the CTC read-out) as one bf16 MXU pass unless told otherwise.  Rounding
# EVERY matmul operand of this model to bf16 moves its log-probs by at most
# 3e-4 over 400-frame utterances (CPU emulation of that rounding against the
# same reference); the limit leaves ~7x headroom over that.
LOGPROB_ATOL = 2e-3
# The 2x2 scale-out reduces the recurrent matmul with a psum across chips
# where the one-chip kernel accumulates in one MXU pass: same tolerance
# argument, both sides carrying bf16-pass rounding.
SCALEOUT_ATOL = 2e-3

SEED = 0           # weights and traffic are made from it
SLOTS = 8          # packed slot grid (one (8, 128) sublane tile of streams)
CHUNK = 16         # frames per engine step: >= 8, so auto picks pallas_seq
N_UTTS = 16
LEN_RANGE = (100, 400)


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    """A phase of the smoke check did not hold."""


def check(ok, what) -> None:
    """Fail the run unless ``ok`` (a raise, not an assert: ``python -O``
    must not strip the checks)."""
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    d = jax.devices()
    return {'platform': d[0].platform, 'kind': d[0].device_kind,
            'count': len(d)}


def make_traffic(n_in: int, n_utts: int) -> list:
    """``n_utts`` utterances of LEN_RANGE frames, made from ``SEED``."""
    rng = np.random.RandomState(SEED)
    lens = rng.randint(LEN_RANGE[0], LEN_RANGE[1] + 1, size=n_utts)
    return [(rng.randn(int(n), n_in) * 0.5).astype(np.float32) for n in lens]


def serve(cfg, params, utts) -> tuple:
    """Serve ``utts`` through ``StreamServer``; returns (engine, per-stream
    log-probs in submission order, compile seconds of the chunk call, its
    compiled HLO text)."""
    server = StreamServer(cfg, params, num_slots=SLOTS, chunk=CHUNK)
    eng = server.engine
    frames = jax.ShapeDtypeStruct((SLOTS, CHUNK, cfg.lstm_inputs), jnp.float32)
    valid = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    t0 = time.perf_counter()
    compiled = eng._fwd.lower(params, eng.states, frames, valid).compile()
    compile_s = time.perf_counter() - t0
    sessions = [server.submit(u) for u in utts]
    done = server.drain()
    check(len(done) == len(utts), f'{len(done)} of {len(utts)} streams done')
    outs = [s.full_log_probs() for s in sessions]
    for u, o in zip(utts, outs):
        check(o.shape == (len(u), cfg.n_outputs), f'log-probs {o.shape}')
        check(np.isfinite(o).all(), 'non-finite log-probs')
    return eng, outs, compile_s, compiled.as_text()


def reference(cfg, params, utts) -> list:
    """Independent f32 reference: the XLA scan at highest matmul precision,
    all utterances in one batch zero-padded at the end (the LSTM is causal,
    so padding after an utterance never reaches its outputs)."""
    ref_cfg = cfg.replace(lstm_backend='xla_scan')
    batch = np.zeros((len(utts), max(map(len, utts)), cfg.lstm_inputs),
                     np.float32)
    for i, u in enumerate(utts):
        batch[i, :len(u)] = u
    with jax.default_matmul_precision('highest'):
        lp = np.asarray(chipmunk_net.forward(ref_cfg, params,
                                             jnp.asarray(batch)))
    return [lp[:len(u), i] for i, u in enumerate(utts)]


def monolithic(cfg, params, utt: np.ndarray) -> np.ndarray:
    """The whole utterance as ONE chunk through the engine's chunk function
    on the engine's pinned backend, in slot 0 of the same slot grid."""
    states, _ = chipmunk_net.init_state(cfg, SLOTS)
    frames = np.zeros((SLOTS, len(utt), cfg.lstm_inputs), np.float32)
    frames[0] = utt
    valid = np.zeros((SLOTS,), np.int32)
    valid[0] = len(utt)
    fn = jax.jit(lambda p, s, f, v: chipmunk_net.stream_forward(
        cfg, p, s, f, valid_len=v)[0])
    return np.asarray(fn(params, states, jnp.asarray(frames),
                         jnp.asarray(valid)))[0]


def one_chip(cfg, params) -> None:
    utts = make_traffic(cfg.lstm_inputs, N_UTTS)
    eng, outs, compile_s, hlo = serve(cfg, params, utts)
    has_kernel = 'tpu_custom_call' in hlo
    log(f'resolved backend: {eng.backend}')
    log(f'chunk call contains tpu_custom_call: {has_kernel}')
    log(f'compile seconds (chunk call, on its own): {compile_s:.3f}')
    log(f'frames served: {sum(len(u) for u in utts)} in {len(utts)} streams '
        f'({SLOTS} slots, chunk {CHUNK})')
    check(eng.backend.startswith('pallas_'),
          f'auto resolved to {eng.backend}, not a Pallas backend')
    check(has_kernel, 'compiled chunk call holds no Mosaic kernel')

    err = max(float(np.abs(o - r).max())
              for o, r in zip(outs, reference(cfg, params, utts)))
    log(f'max |log-prob - f32 reference|: {err:.3e} (limit {LOGPROB_ATOL:g})')
    check(err <= LOGPROB_ATOL, f'f32 reference error {err} > {LOGPROB_ATOL}')

    mono = monolithic(eng.cfg, params, utts[0])
    equal = bool(np.array_equal(mono, outs[0]))
    log(f'chunked == monolithic on {eng.backend} (stream 0, '
        f'{len(utts[0])} frames): {equal}')
    check(equal, 'chunked differs from monolithic by '
          f'{float(np.abs(mono - outs[0]).max())}')


def four_chips(cfg, params) -> None:
    from repro.core import systolic
    from repro.launch.mesh import install_systolic_topology
    utts = make_traffic(cfg.lstm_inputs, N_UTTS // 2)

    # one-chip pallas_seq engine: what the scale-out is compared with
    _, base, _, _ = serve(cfg.replace(lstm_backend='pallas_seq'), params, utts)

    mesh = install_systolic_topology('2x2')
    devs = set(mesh.devices.flat)
    log(f'2x2 mesh: {dict(mesh.shape)} over {len(devs)} distinct devices')
    check(len(devs) == 4, f'2x2 mesh spans {len(devs)} devices')
    admitted = systolic.seq_scaleout_admissible(cfg.lstm_hidden, mesh)
    log(f'seq_scaleout_admissible({cfg.lstm_hidden}, 2x2): {admitted}')
    check(admitted, 'the 2x2 preset refuses the 421-wide layer')
    eng, outs, compile_s, hlo = serve(cfg, params, utts)
    log(f'resolved backend: {eng.backend}')
    log(f'compile seconds (chunk call, on its own): {compile_s:.3f}')
    check(eng.backend == 'pallas_seq_systolic', f'resolved {eng.backend}')
    collectives = {op: op in hlo for op in ('all-reduce', 'all-gather')}
    log(f'collectives in the chunk call: {collectives}')
    check(all(collectives.values()), f'collectives {collectives}')
    log(f'frames served: {sum(len(u) for u in utts)} in {len(utts)} streams')
    err = max(float(np.abs(o - b).max()) for o, b in zip(outs, base))
    log(f'max |2x2 scale-out - one-chip pallas_seq|: {err:.3e} '
        f'(limit {SCALEOUT_ATOL:g})')
    check(err <= SCALEOUT_ATOL, f'scale-out error {err} > {SCALEOUT_ATOL}')
    systolic.clear_mesh()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--four-chips', action='store_true',
                    help='run only the 2x2 systolic scale-out on four chips '
                         'and compare it with the one-chip pallas_seq path')
    args = ap.parse_args(argv)

    info = device_info()
    log(f'platform: {info["platform"]}  device_kind: {info["kind"]}  '
        f'count: {info["count"]}')
    if info['platform'] != 'tpu':
        print('chip_smoke: no TPU found; this check runs only on the chip',
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if info['count'] < need:
        print(f'chip_smoke: needs {need} chips, found {info["count"]}',
              file=sys.stderr)
        return 1
    log(f'compile cache: {enable_compile_cache()}')

    cfg = configs.get_config('chipmunk-ctc')
    check(cfg.lstm_backend == 'auto', f'config backend {cfg.lstm_backend}')
    params, _ = get_bundle(cfg).init(jax.random.PRNGKey(SEED))
    log(f'model: {cfg.name} {cfg.lstm_inputs} -> {cfg.n_layers}x'
        f'{cfg.lstm_hidden} -> {cfg.n_outputs}')
    if args.four_chips:
        four_chips(cfg, params)
    else:
        one_chip(cfg, params)
    print(json.dumps({'ok': True, 'device': info}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
