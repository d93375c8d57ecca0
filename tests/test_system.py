"""End-to-end system tests: training drivers, serving, dry-run machinery."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import REPO, SRC, run_with_devices


def _run_cli(args, timeout=900):
    import os
    env = dict(os.environ)
    env['PYTHONPATH'] = SRC + os.pathsep + env.get('PYTHONPATH', '')
    proc = subprocess.run([sys.executable] + args, env=env, cwd=str(REPO),
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, f'{args}\n{proc.stdout}\n{proc.stderr}'
    return proc.stdout


def test_train_driver_end_to_end(tmp_path):
    """Full loop: data -> sharded step -> checkpoint -> resume."""
    out = _run_cli(['-m', 'repro.launch.train', '--arch', 'chipmunk-ctc',
                    '--smoke', '--steps', '8', '--batch', '4', '--seq', '32',
                    '--ckpt-every', '4', '--ckpt-dir', str(tmp_path)])
    assert 'done' in out
    out2 = _run_cli(['-m', 'repro.launch.train', '--arch', 'chipmunk-ctc',
                     '--smoke', '--steps', '12', '--batch', '4', '--seq', '32',
                     '--ckpt-dir', str(tmp_path), '--resume'])
    assert 'resumed at step 8' in out2


def test_serve_driver_end_to_end():
    out = _run_cli(['-m', 'repro.launch.serve', '--arch', 'qwen3-14b',
                    '--smoke', '--requests', '3', '--slots', '2',
                    '--max-new', '3'])
    assert 'served 3 requests' in out


def test_lm_train_loss_decreases():
    """~1M-param transformer trains for 25 steps; loss must drop."""
    out = _run_cli(['examples/train_lm.py', '--tiny', '--steps', '25',
                    '--ckpt-dir', '/tmp/repro_test_lm'])
    lines = [l for l in out.splitlines() if l.startswith('step')]
    first = float(lines[0].split('loss')[1].split()[0])
    last = float(lines[-1].split('loss')[1].split()[0])
    assert last < first - 0.5, out


@pytest.mark.skipif(
    (os.cpu_count() or 1) < 8,
    reason='environment-gated: SPMD-compiling an LM train cell over the '
           '256-chip production mesh segfaults the XLA CPU compiler on small '
           'hosts (observed on 2-core CI boxes); the dry-run path itself is '
           'covered by test_dryrun_single_cell_small_host below')
def test_dryrun_single_cell_multidevice():
    """Lower+compile one (arch x shape) cell on the production mesh in a
    subprocess with 512 placeholder devices; checks the full dry-run path."""
    out = run_with_devices("""
from repro.launch.dryrun import lower_cell
rec = lower_cell('whisper-base', 'train_4k', multi_pod=False)
assert rec['status'] == 'ok', rec
assert rec['roofline']['flops'] > 0
assert rec['roofline']['bottleneck'] in ('compute', 'memory', 'collective')
print('OK', rec['roofline']['bottleneck'])
""", n_devices=512, timeout=900)
    assert 'OK' in out


def test_dryrun_single_cell_small_host():
    """Same dry-run path (lower+compile+roofline on the production mesh) with
    the paper's own CTC cell — small enough to SPMD-compile on any host."""
    out = run_with_devices("""
from repro.launch.dryrun import lower_cell
rec = lower_cell('chipmunk-ctc', 'train_4k', multi_pod=False)
assert rec['status'] == 'ok', rec
assert rec['roofline']['flops'] > 0
assert rec['roofline']['bottleneck'] in ('compute', 'memory', 'collective')
print('OK', rec['roofline']['bottleneck'])
""", n_devices=512, timeout=900)
    assert 'OK' in out


def test_dryrun_multipod_cell():
    out = run_with_devices("""
from repro.launch.dryrun import lower_cell
rec = lower_cell('xlstm-1.3b', 'decode_32k', multi_pod=True)
assert rec['status'] == 'ok', rec
assert rec['n_chips'] == 512
print('OK')
""", n_devices=512, timeout=900)
    assert 'OK' in out


def test_production_mesh_shapes():
    out = run_with_devices("""
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
m2 = make_production_mesh(multi_pod=True)
assert dict(zip(m1.axis_names, m1.devices.shape)) == {'data': 16, 'model': 16}
assert dict(zip(m2.axis_names, m2.devices.shape)) == {
    'pod': 2, 'data': 16, 'model': 16}
print('OK')
""", n_devices=512)
    assert 'OK' in out


def test_long_context_skip_policy():
    """long_500k runs only for sub-quadratic archs (DESIGN.md §4a)."""
    from repro import configs
    runnable = {a for a in configs.ASSIGNED_ARCHS
                if any(s.name == 'long_500k'
                       for s in configs.shapes_for(configs.get_config(a)))}
    assert runnable == {'xlstm-1.3b', 'hymba-1.5b', 'mixtral-8x22b'}


def test_cell_count():
    """10 assigned archs x shapes = 33 runnable cells (40 minus 7 documented
    long_500k skips) + 3 chipmunk-ctc cells."""
    from repro.launch.dryrun import all_cells
    cells = all_cells()
    assert len(cells) == 36
    assert len([c for c in cells if c[0] != 'chipmunk-ctc']) == 33
