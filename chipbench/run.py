"""Run one benchmark cell once on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
``BENCHMARK.json`` at the checkout's root.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``, each compared number beside its limit.  Everything else goes to
standard error.  Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault('TPU_LOG_DIR', 'disabled')
sys.path.insert(0, str(ROOT / 'src'))
sys.path.insert(0, str(ROOT))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench.bench import Spec
    spec = Spec(ROOT)
    cell = spec.cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu' or len(devices) < cell['chips']:
        print(f'chipbench: {cell["name"]} needs {cell["chips"]} TPU chip(s); '
              f'found {len(devices)} {devices[0].platform} device(s)',
              file=sys.stderr)
        return 2
    used = devices[:cell['chips']]
    peaks = spec.peaks(used[0].device_kind)

    from chipbench.harness import run_cell
    result = run_cell(spec, cell, seed=args.seed, seconds=args.seconds,
                      traced=bool(args.trace), t_start=T_START, peaks=peaks)
    result['device'] = {'platform': used[0].platform,
                        'kind': used[0].device_kind, 'count': len(used),
                        **result['device']}
    checks = result.pop('checks')
    result['checks'] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
