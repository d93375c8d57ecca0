"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; ``--json`` additionally writes
the rows (plus environment metadata) to a JSON file so the perf trajectory is
tracked PR over PR.

  table1_efficiency   — Table 1 (peak perf / energy / area efficiency)
  table2_ctc          — Table 2 (CTC-3L-421H-UNI on 3 tile configs, 2 voltages)
  fig5_shmoo          — Fig. 5 (voltage shmoo curves)
  systolic_equivalence— Sec. 3 dataflow equivalence + int8 accuracy/timing
  kernel_bench        — kernel-layer reference timings (incl. the per-step vs
                        whole-sequence LSTM kernel comparison and the
                        layerwise vs fused whole-stack wavefront rows)
  systolic_scaleout   — DESIGN.md §6: per-step vs persistent *distributed*
                        execution on a multi-device mesh (subprocess with a
                        forced host device count), incl. a scaled-down
                        graves-75 configuration
  streaming           — DESIGN.md §7: packed multi-stream engine vs the
                        per-slot batch-1 serving baseline on the 123→421
                        CTC topology
  roofline_report     — roofline table from the multi-pod dry-run artifacts

  python -m benchmarks.run --suite kernels --json BENCH_kernels.json
  python -m benchmarks.run --suite scaleout --json BENCH_systolic.json
  python -m benchmarks.run --suite streaming --json BENCH_streaming.json
"""
import argparse
import json
import platform


def _suites():
    from . import (fig5_shmoo, kernel_bench, roofline_report, streaming,
                   systolic_equivalence, systolic_scaleout, table1_efficiency,
                   table2_ctc)
    return {
        'table1': table1_efficiency.run,
        'table2': table2_ctc.run,
        'fig5': fig5_shmoo.run,
        'systolic': systolic_equivalence.run,
        'kernels': kernel_bench.run,
        'scaleout': systolic_scaleout.run,
        'streaming': streaming.run,
        'roofline': roofline_report.run,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--suite', action='append', default=None,
                    help='suite name(s); default: all')
    ap.add_argument('--json', nargs='?', const='BENCH_kernels.json',
                    default=None, metavar='PATH',
                    help='also write results to a JSON file')
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import enable_compile_cache

    from . import common

    enable_compile_cache()
    common.RESULTS.clear()        # idempotent across in-process invocations
    suites = _suites()
    names = args.suite or list(suites)
    unknown = [n for n in names if n not in suites]
    if unknown:
        raise SystemExit(f'unknown suite(s) {unknown}; have {list(suites)}')

    print('name,us_per_call,derived')
    for n in names:
        suites[n]()

    if args.json:
        payload = {
            'backend': jax.default_backend(),
            'device_count': jax.device_count(),
            'jax_version': jax.__version__,
            'python': platform.python_version(),
            'suites': names,
            'results': common.RESULTS,
        }
        with open(args.json, 'w') as f:
            json.dump(payload, f, indent=2)
        print(f'wrote {len(common.RESULTS)} rows to {args.json}')


if __name__ == '__main__':
    main()
