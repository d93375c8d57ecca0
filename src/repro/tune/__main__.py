"""Offline schedule tuner CLI.

``python -m repro.tune --out tuned_schedules.json`` shmoos the schedule
space, records the winners, and writes the cache + the shared-format shmoo
CSV.  Default is predicted-only (deterministic, no timing — what CI runs
twice to assert replay stability); ``--measure`` adds interleaved timed
trials for the single-device decisions, and ``--staged-devices N`` spawns a
subprocess with N forced host devices to measure the staged ``(Tc,
in_stage)`` schedule on a real mesh (the driver process must keep seeing
one device — same pattern as benchmarks/systolic_scaleout.py).

``--geometry`` switches from schedule tuning to GEOMETRY tuning (DESIGN.md
§13): instead of shmooing (Tc, in_stage) on the fixed ``--stages x (--rows
x --cols)`` placement, it shmoos the placement itself — every mesh shape
and per-stage layer split inside the ``--devices`` budget — with the fixed
placement as the balanced-default reference.  Predicted-only by default;
with ``--staged-devices`` the trial measures on forced host devices,
asserting bit-equality within the reference's arithmetic class first
(``--allow-reassoc`` opts the allclose-gated cross-class candidates in).

``--placements SxRxC[,SxRxC...]`` measures several staged placements in one
run; a placement that exceeds ``--staged-devices`` is SKIPPED with a
warning in this batch mode, while a single over-budget request is a hard
error — either way you get an actionable message, never a raw shard_map
failure from inside the subprocess.
"""
import argparse
import json
import pathlib
import subprocess
import sys


_STAGED_TUNE_SNIPPET = r"""
import json, sys
import jax
from repro.core import lstm, systolic
from repro.tune import ScheduleCache, tune_staged_stack

n_x, n_h, L, T, B = {n_x}, {n_h}, {L}, {T}, {B}
stack = lstm.init_lstm_stack(jax.random.PRNGKey(42), n_x, n_h, L)
xs = jax.random.normal(jax.random.PRNGKey(43), (T, B, n_x)) * 0.5
mesh = systolic.make_systolic_mesh({rows}, {cols}, stage={stages})
cache = ScheduleCache()
entry, _ = tune_staged_stack(stack, mesh, xs, cache=cache, iters={iters})
print('CACHE|' + json.dumps(cache.to_json()))
"""

_GEOMETRY_TUNE_SNIPPET = r"""
import json, sys
import jax
from repro.core import lstm
from repro.tune import ScheduleCache
from repro.tune.autotune import tune_geometry

n_x, n_h, L, T, B = {n_x}, {n_h}, {L}, {T}, {B}
stack = lstm.init_lstm_stack(jax.random.PRNGKey(42), n_x, n_h, L)
xs = jax.random.normal(jax.random.PRNGKey(43), (T, B, n_x)) * 0.5
cache = ScheduleCache()
entry, records, base = tune_geometry(
    stack, xs, devices={devices}, ref=({stages}, {rows}, {cols}),
    cache=cache, iters={iters}, allow_reassoc={allow_reassoc})
print('CACHE|' + json.dumps(cache.to_json()))
print('GEO|' + json.dumps(
    {{'baseline_us': base, 'measured_us': entry.measured_us,
      'stages': entry.stages, 'rows': entry.rows, 'cols': entry.cols,
      'blocks': entry.blocks, 'tc': entry.tc,
      'in_stage': entry.in_stage}}))
"""


def _device_budget_error(stages: int, rows: int, cols: int,
                         devices: int) -> str:
    """Actionable message when a requested placement exceeds the forced
    device budget — the check runs BEFORE the subprocess so the user sees
    this instead of a raw shard_map error (None = placement fits)."""
    need = stages * rows * cols
    if devices >= need:
        return ''
    return (f'mesh stage:{stages} x (row:{rows} x col:{cols}) needs {need} '
            f'devices but --staged-devices={devices}; pass '
            f'--staged-devices >= {need} or shrink --stages/--rows/--cols')


def _run_tune_subprocess(snippet: str, devices: int):
    from ..launch.mesh import host_device_env
    proc = subprocess.run([sys.executable, '-c', snippet],
                          env=host_device_env(devices),
                          capture_output=True, text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(f'tune subprocess failed\nSTDOUT:\n'
                           f'{proc.stdout}\nSTDERR:\n{proc.stderr}')
    return proc.stdout


def _merge_cache_stdout(stdout: str, cache):
    from .schedule import ScheduleCache
    extra = {}
    for line in stdout.splitlines():
        if line.startswith('CACHE|'):
            sub = ScheduleCache.from_json(json.loads(line[6:]))
            for e in sub.entries():
                cache.record(e)
        elif line.startswith('GEO|'):
            extra = json.loads(line[4:])
    return extra


def _measure_staged(args, cache, stages: int, rows: int, cols: int):
    snippet = _STAGED_TUNE_SNIPPET.format(
        n_x=args.n_x, n_h=args.n_h, L=args.layers, T=args.T, B=args.B,
        rows=rows, cols=cols, stages=stages, iters=args.iters)
    _merge_cache_stdout(
        _run_tune_subprocess(snippet, args.staged_devices), cache)
    return cache


def _parse_placements(spec: str):
    out = []
    for part in spec.split(','):
        dims = part.lower().split('x')
        if len(dims) != 3 or not all(d.isdigit() and int(d) >= 1
                                     for d in dims):
            raise SystemExit(f'bad --placements entry {part!r}: expected '
                             f'SxRxC with positive integers, e.g. 2x5x5')
        out.append(tuple(int(d) for d in dims))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(prog='python -m repro.tune')
    ap.add_argument('--out', default='tuned_schedules.json',
                    help='schedule-cache JSON to write')
    ap.add_argument('--csv', default=None,
                    help='also write the shmoo records (shared CSV format)')
    ap.add_argument('--measure', action='store_true',
                    help='run interleaved timed trials for the '
                         'single-device decisions (default: predicted-only)')
    ap.add_argument('--staged-devices', type=int, default=0,
                    help='measure the staged/geometry schedule in a '
                         'subprocess with this many forced host devices '
                         '(0 = predicted-only)')
    ap.add_argument('--geometry', action='store_true',
                    help='tune the mesh GEOMETRY (stages x rows x cols + '
                         'stage split) for the --devices budget instead of '
                         'only the schedule of the fixed placement')
    ap.add_argument('--devices', type=int, default=0,
                    help='device budget for --geometry (default: '
                         '--staged-devices, else stages*rows*cols)')
    ap.add_argument('--allow-reassoc', action='store_true',
                    help='let the measured geometry trial cross arithmetic '
                         'classes (allclose-gated; default stays inside '
                         'the bit-equal class of the reference)')
    ap.add_argument('--placements', default=None,
                    help='comma-separated SxRxC staged placements to '
                         'measure in one run (over-budget entries are '
                         'skipped with a warning)')
    ap.add_argument('--n-x', type=int, default=48)
    ap.add_argument('--n-h', type=int, default=96)
    ap.add_argument('--layers', type=int, default=3)
    ap.add_argument('--T', type=int, default=32)
    ap.add_argument('--B', type=int, default=4)
    ap.add_argument('--stages', type=int, default=2)
    ap.add_argument('--rows', type=int, default=2)
    ap.add_argument('--cols', type=int, default=2)
    ap.add_argument('--iters', type=int, default=3)
    ap.add_argument('--tile', type=int, default=None,
                    help='systolic plan tile for the int8 trial (default '
                         'min(n_h, 128))')
    args = ap.parse_args(argv)

    from .autotune import (replay_check, tune_geometry,
                           tune_quantized_backend, tune_stack_lb)
    from .schedule import ANY_MESH, ScheduleCache, ScheduleEntry
    from .shmoo import (geometry_shmoo_records, staged_shmoo_records,
                        write_shmoo_csv)

    cache = ScheduleCache()
    out = pathlib.Path(args.out)
    if out.exists():            # tuning refines, never forgets
        cache = ScheduleCache.load(out)

    budget = args.devices or args.staged_devices \
        or args.stages * args.rows * args.cols

    # Fail fast on an impossible placement request (S2): the check runs
    # BEFORE any tuning so the user sees the actionable message, not a raw
    # shard_map error minutes in.  Batch (--placements) requests validate
    # per entry inside the loop — over-budget entries skip, not crash.
    if args.staged_devices:
        if args.geometry:
            if args.staged_devices < budget:
                raise SystemExit(
                    f'--devices={budget} exceeds '
                    f'--staged-devices={args.staged_devices}; the forced '
                    f'host must hold the whole budget')
            err = _device_budget_error(args.stages, args.rows, args.cols,
                                       budget)
            if err:
                raise SystemExit(f'reference placement over budget: {err}')
        elif not args.placements:
            err = _device_budget_error(args.stages, args.rows, args.cols,
                                       args.staged_devices)
            if err:
                raise SystemExit(err)

    # int8 backend decision at the requested shape
    entry, q_records = tune_quantized_backend(
        args.n_x, args.n_h, args.layers, args.T, args.B, cache=cache,
        tile=args.tile, measure=args.measure, iters=args.iters)
    print(f'q_stack_backend -> {entry.backend} ({entry.source})')

    # §8 single-engine lb streaming factor
    lb_ent, lb_records = tune_stack_lb(
        args.n_x, args.n_h, args.layers, args.T, args.B, cache=cache,
        measure=args.measure, iters=args.iters)
    if lb_ent is not None:
        print(f'stack_lb -> lb={lb_ent.lb} ({lb_ent.source})')

    if args.geometry:
        records = geometry_shmoo_records(args.n_x, args.n_h, args.layers,
                                         args.T, args.B, devices=budget)
        if not args.staged_devices:
            import jax
            import jax.numpy as jnp
            from ..core.lstm import init_lstm_stack
            stack = init_lstm_stack(jax.random.PRNGKey(42), args.n_x,
                                    args.n_h, args.layers)
            xs = jnp.zeros((args.T, args.B, args.n_x))
            ent, _, _ = tune_geometry(stack, xs, devices=budget,
                                      ref=(args.stages, args.rows,
                                           args.cols),
                                      cache=cache, measure=False)
            print(f'geometry -> {ent.stages}x({ent.rows}x{ent.cols}) '
                  f'blocks={ent.blocks} Tc={ent.tc} '
                  f'in_stage={ent.in_stage} (predicted)')
        else:
            snippet = _GEOMETRY_TUNE_SNIPPET.format(
                n_x=args.n_x, n_h=args.n_h, L=args.layers, T=args.T,
                B=args.B, devices=budget, stages=args.stages,
                rows=args.rows, cols=args.cols, iters=args.iters,
                allow_reassoc=bool(args.allow_reassoc))
            geo = _merge_cache_stdout(
                _run_tune_subprocess(snippet, args.staged_devices), cache)
            if geo:
                speedup = (geo['baseline_us'] / geo['measured_us']
                           if geo['measured_us'] else 0.0)
                print(f"geometry -> {geo['stages']}x({geo['rows']}x"
                      f"{geo['cols']}) blocks={geo['blocks']} "
                      f"Tc={geo['tc']} in_stage={geo['in_stage']} "
                      f"(measured, {geo['measured_us'] / 1e3:.1f} ms, "
                      f"{speedup:.2f}x balanced ref)")
    else:
        # staged schedule: predicted shmoo always; measured when devices
        records = staged_shmoo_records(args.n_x, args.n_h, args.layers,
                                       args.T, args.B, stages=args.stages,
                                       rows=args.rows, cols=args.cols)
        if records and not args.staged_devices:
            p = records[0].params
            cache.record(ScheduleEntry(
                kind='stack_f32', n_x=args.n_x, n_h=args.n_h,
                n_layers=args.layers, T=args.T, B=args.B,
                mesh=f'stage:{args.stages},row:{args.rows},'
                     f'col:{args.cols}',
                tc=int(p['tc']), in_stage=str(p['in_stage']),
                bn=int(p['bn']), bk=int(p['bk']), lb=int(p['lb']),
                predicted_us=records[0].metrics['predicted_us'],
                source='predicted'))
            print(f"staged schedule -> Tc={p['tc']} "
                  f"in_stage={p['in_stage']} (predicted)")
        if args.staged_devices:
            placements = (_parse_placements(args.placements)
                          if args.placements
                          else [(args.stages, args.rows, args.cols)])
            batch = len(placements) > 1
            for stages, rows, cols in placements:
                err = _device_budget_error(stages, rows, cols,
                                           args.staged_devices)
                if err:
                    if not batch:
                        raise SystemExit(err)
                    print(f'skipping {stages}x({rows}x{cols}): {err}',
                          file=sys.stderr)
                    continue
                _measure_staged(args, cache, stages, rows, cols)
                ent = cache.lookup(
                    'stack_f32', n_x=args.n_x, n_h=args.n_h,
                    n_layers=args.layers, T=args.T, B=args.B,
                    mesh=f'stage:{stages},row:{rows},col:{cols}')
                print(f'staged schedule {stages}x({rows}x{cols}) -> '
                      f'Tc={ent.tc} in_stage={ent.in_stage} (measured, '
                      f'{ent.measured_us / 1e3:.1f} ms)')

    n = replay_check(cache)
    print(f'replay check: {n} staged entries stable')
    cache.save(out)
    print(f'wrote {len(cache)} entries -> {out}')
    if args.csv:
        for r in q_records:
            r.metrics.setdefault('predicted_us', 0.0)
        if q_records:
            write_shmoo_csv(pathlib.Path(args.csv).with_suffix('.q.csv'),
                            q_records)
        if lb_records:
            write_shmoo_csv(pathlib.Path(args.csv).with_suffix('.lb.csv'),
                            lb_records)
        if records:
            write_shmoo_csv(args.csv, records)
            print(f'wrote {len(records)} shmoo points -> {args.csv}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
