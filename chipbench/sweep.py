"""Knee sweep of a live cell: the highest offered rate it sustains.

    python3 chipbench/sweep.py --workload ctc421-c16.live --seed 1 \\
        --seconds 20 --rates 20,30,40,50

For each rate, in this one process, a fresh engine serves the cell's live
mix at that rate: the mix's warm-up, then a window of ``--seconds``.  A
rate is sustained when ``emit_p95_ms`` stays within twice the real-time
budget of a chunk (chunk x frame period: waiting up to one period for the
tick, then processing within one) and the admission queue does not grow
over the window.  The knee is the highest sustained rate below the first
that is not.  One JSON line per rate, then one with the knee, on standard
output.  Needs a TPU; the benchmark's cells are not run by it.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault('TPU_LOG_DIR', 'disabled')
sys.path.insert(0, str(ROOT / 'src'))
sys.path.insert(0, str(ROOT))


def queue_grows(pending) -> bool:
    """Whether the admission queue's last quarter of the window is longer,
    on average, than its first quarter by more than one stream."""
    q = max(1, len(pending) // 4)
    return sum(pending[-q:]) / q > sum(pending[:q]) / q + 1


def measure(spec, cell, rate: float, seed: int, seconds: float) -> dict:
    import gc
    import jax
    import numpy as np
    from chipbench import harness, loop

    config = spec.config(cell)
    mix = dict(spec.traffic(cell), rate_per_s=rate)
    serving = config['serving']
    kind = spec.model(config)
    weights = jax.block_until_ready(kind.make_weights(config['model'], seed))
    server = harness.start_server(config, kind, weights)
    sched = harness.schedule_for(mix, config, seed, seconds)
    em = loop.Emissions(serving['frame_ms'] / 1000)

    def on_finish(_sessions, _in_window):
        server.engine.sched.done.clear()

    window = harness.drive(server.engine, mix, config, sched, em,
                           seconds=seconds, on_finish=on_finish)
    lat = em.all_latencies() * 1e3
    budget_ms = serving['chunk'] * serving['frame_ms']
    p95 = float(np.percentile(lat, 95))
    grows = queue_grows(window.pending)
    out = {'rate_per_s': rate, 'emit_p95_ms': p95,
           'emit_p50_ms': float(np.percentile(lat, 50)),
           'limit_ms': 2 * budget_ms, 'queue_first': int(window.pending[0]),
           'queue_last': int(window.pending[-1]), 'queue_grows': grows,
           'mean_streams': float(np.mean(em.step_streams)),
           'steps': window.steps,
           'sustained': p95 <= 2 * budget_ms and not grows}
    del server, em
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--rates', required=True,
                    help='comma-separated offered rates, streams per second')
    args = ap.parse_args(argv)

    from chipbench.bench import Spec
    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    if spec.traffic(cell)['kind'] != 'live':
        print('sweep: only live mixes have an offered rate', file=sys.stderr)
        return 2
    import jax
    if jax.devices()[0].platform != 'tpu':
        print('sweep: no TPU found', file=sys.stderr)
        return 2
    from chipbench.harness import enable_cache
    enable_cache()
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(',')):
        row = measure(spec, cell, rate, args.seed, args.seconds)
        print(json.dumps(row), flush=True)
        if not row['sustained']:
            break
        knee = rate
    print(json.dumps({'workload': cell['name'], 'knee_per_s': knee,
                      'offered_per_s': None if knee is None else 0.8 * knee}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
