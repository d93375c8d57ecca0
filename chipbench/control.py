"""The lower-precision control of a cell's comparison, read on the chip.

    python3 chipbench/control.py --workload ctc421-c16.live \\
        --seeds 11,12,13

For each seed, in this one process: the cell's weights and traffic from
the seed, a sample of its streams drawn as a run draws one
(``sample_streams`` of them and the longest), and in the program's place
the reference computed in bfloat16, the nearest precision below the
configuration's float32.  The control's log-probs go through a run's own
comparison and limits (``check.numbers``, ``check.judge``).  One JSON line
per seed on standard output, with each number beside its limit and
``correct``, which a sound comparison reads as false.  Needs a TPU; the
benchmark's own runs never run it.
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

# streams a window of any cell finishes, at most: the sample is drawn
# among the first of these
WINDOW_STREAMS = 20000


def read(spec, cell: dict, seed: int) -> dict:
    """The control's comparison for one seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import check, harness

    config, mix = spec.config(cell), spec.traffic(cell)
    frame_s = config['serving']['frame_ms'] / 1000
    kind = spec.model(config)
    weights = jax.block_until_ready(kind.make_weights(config['model'], seed))
    sched = harness.schedule_for(mix, config, seed,
                                 seconds=spec.data['run_seconds'])
    n = min(len(sched), WINDOW_STREAMS)
    rng = np.random.default_rng([seed, 3])
    pick = list(rng.choice(n, size=min(n, mix['sample_streams']),
                           replace=False))
    longest = int(np.argmax(sched.length[:n]))
    if longest not in pick:
        pick.append(longest)
    frames = [sched.frames(i) for i in pick]
    rows = mix['sample_streams'] + 1
    width = int(round(mix['length_s']['max'] / frame_s))
    reference = spec.reference(config['reference'])
    served = [(f, None, None) for f in frames]
    ref_lp = check.reference_log_probs(reference, config, weights, served,
                                       rows, width)
    ctl_lp = check.reference_log_probs(reference, config, weights, served,
                                       rows, width, dtype=jnp.bfloat16)
    values = check.numbers([(f, ctl_lp[i, :kind.outputs(len(f))], None)
                            for i, f in enumerate(frames)], ref_lp,
                           kind.outputs)
    correct, checks = check.judge(values, config['limits'])
    return {'workload': cell['name'], 'seed': seed, 'correct': correct,
            'streams': len(frames), 'frames': sum(len(f) for f in frames),
            'read': {k: v for k, v in values.items() if k not in checks},
            'checks': checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True,
                    help='comma-separated seeds')
    args = ap.parse_args(argv)
    from chipbench.bench import Spec
    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    import jax
    if jax.devices()[0].platform != 'tpu':
        print('control: no TPU found', file=sys.stderr)
        return 2
    from chipbench.harness import enable_cache
    enable_cache()
    for seed in (int(s) for s in args.seeds.split(',')):
        print(json.dumps(read(spec, cell, seed)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
