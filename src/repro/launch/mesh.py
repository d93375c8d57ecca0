"""Production mesh construction (TPU v5e pods; CPU placeholder devices OK).

Single pod: 16 x 16 = 256 chips ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips ("pod", "data", "model") — the "pod" axis
crosses DCI; sharding anything over it proves the config scales past one pod.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

# TPU v5e constants used for the roofline analysis (per assignment).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s per chip
HBM_BW = 819e9                # B/s per chip
ICI_BW = 50e9                 # B/s per link


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The (data, model) training mesh shape the launch scripts assume —
    one 16x16 pod slice, or two pods under an extra leading 'pod' axis.
    Topology construction only; no placement or arithmetic happens here."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ('pod', 'data', 'model') if multi_pod else ('data', 'model')
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


# ---------------------------------------------------------------------------
# Systolic topology presets (DESIGN.md §6 and §9)
# ---------------------------------------------------------------------------
# name -> (stage, rows, cols) engine grids from the paper's scaling study.
# ``stage == 1`` presets drive the persistent scale-out kernel
# (core/systolic.systolic_lstm_seq); ``stage > 1`` presets drive the STAGED
# scale-out of the fused wavefront stack
# (core/systolic.systolic_lstm_stack_seq, backend
# ``pallas_seq_fused_systolic``): each stage holds one contiguous layer
# block, chunks pipeline stage to stage via ppermute.  'graves-75' is the
# 75-tile 3x(5x5) configuration that runs the Graves phoneme topology in
# real time (paper Sec. 4.2, Table 2) — runnable end to end with host
# devices via XLA_FLAGS=--xla_force_host_platform_device_count=75 (see the
# README serving command).
SYSTOLIC_TOPOLOGIES = {
    # degenerate single-engine preset: never auto-picked (an all-1 mesh is
    # inadmissible, §6.2) — use with an explicit backend= selection
    'single': (1, 1, 1),
    '1x2': (1, 1, 2),        # smallest col (partial-sum hop) scale-out
    '2x1': (1, 2, 1),        # smallest row (h re-broadcast) scale-out
    '2x2': (1, 2, 2),
    '5x5': (1, 5, 5),        # the paper's single-layer 25-tile config
    '5x7': (1, 5, 7),        # CTC-3L-421H layer plan at tile=96 (35 engines)
    'graves-75': (3, 5, 5),  # 3-stage pipeline of 5x5 grids = 75 tiles
}


def host_device_env(n_devices: int) -> Dict[str, str]:
    """Environment for a child process that emulates an ``n_devices`` mesh
    on forced host (CPU) devices, with this checkout's ``src`` importable.

    Refused on a TPU: the parent holds the chip, so a child that reaches
    for it fails or hangs — and a mesh of emulated CPU devices says nothing
    about the chip.  Run such emulations with ``JAX_PLATFORMS=cpu``.
    """
    if jax.default_backend() == 'tpu':
        raise RuntimeError(
            'forced-host-device emulation is CPU-only: this process holds '
            'the TPU, so a child process cannot use it; rerun with '
            'JAX_PLATFORMS=cpu')
    env = dict(os.environ)
    env['XLA_FLAGS'] = f'--xla_force_host_platform_device_count={n_devices}'
    src = str(pathlib.Path(__file__).resolve().parents[2])
    env['PYTHONPATH'] = src + os.pathsep + env.get('PYTHONPATH', '')
    return env


def make_systolic_topology(name: str, devices=None) -> Mesh:
    """Build the named preset as a ('stage','row','col') mesh."""
    stage, rows, cols = SYSTOLIC_TOPOLOGIES[name]
    from ..core.systolic import make_systolic_mesh
    return make_systolic_mesh(rows, cols, stage=stage, devices=devices)


def install_systolic_topology(name: str, devices=None) -> Mesh:
    """Build the named preset and install it as the process systolic mesh.

    After installation, ``auto`` LSTM backend selection resolves to
    ``pallas_seq_systolic`` for layers a stage-1 mesh admits (DESIGN.md
    §6), and stack-level selection resolves to the staged
    ``pallas_seq_fused_systolic`` for stacks a ``stage > 1`` mesh admits
    (DESIGN.md §9 — ``graves-75`` runs the full 3x(5x5) Table-2 topology
    in one dispatch path).  Inadmissible presets are installed but never
    auto-picked (e.g. the all-1 ``single`` mesh: the single-engine §3.3
    rules keep deciding there; explicit ``backend=`` selection still
    works).
    """
    from ..core import systolic
    return systolic.install_mesh(make_systolic_topology(name, devices))


# ---------------------------------------------------------------------------
# Two-level die/tile fault-domain hierarchy (DESIGN.md §14)
# ---------------------------------------------------------------------------
# The Chipmunk follow-up ("Vau da Muntanialas", PAPERS.md) scales the same
# systolic idea across DIES with an explicit interconnect hierarchy: intra-die
# collectives are cheap, inter-die hops are chunk-granular.  ``DieMesh`` models
# that hierarchy as a ("die", "stage", "row", "col") fleet: each die owns
# ``stage`` pipeline stages of (rows x cols) engine grids, and the die axis is
# the FAULT-DOMAIN axis — a die failure kills exactly its sub-mesh, and the
# systolic array re-forms on the survivors.  Execution flattens the healthy
# dies onto the existing ("stage", "row", "col") staged dispatch path (the
# die and stage axes compose into one pipeline axis: total stages =
# healthy_dies * stage), so every degraded rung keeps the per-stage
# (rows x cols) grid geometry — the same arithmetic class (n_h_p, bk), which
# is what makes die-level degrade AND canary-validated promote bit-preserving
# (tests/test_recovery.py).


@dataclasses.dataclass(frozen=True)
class DieMesh:
    """Two-level ("die", "stage", "row", "col") fleet model.

    ``dies`` fault domains, each holding ``stage`` pipeline stages of
    (``rows`` x ``cols``) engine grids; ``devices`` is the row-major flat
    device tuple (die-major, so one die's devices are contiguous — a die
    failure maps to a contiguous device range).  Pure topology bookkeeping:
    no arithmetic of its own — execution goes through ``submesh``'s
    flattened projection onto the staged scale-out path.
    """

    dies: int
    stage: int
    rows: int
    cols: int
    devices: Tuple = ()

    @property
    def engines_per_die(self) -> int:
        """Engines one die contributes (= engines lost when it fails)."""
        return self.stage * self.rows * self.cols

    @property
    def n_engines(self) -> int:
        """Total fleet engines across all dies."""
        return self.dies * self.engines_per_die

    def die_devices(self, die: int) -> Tuple:
        """The contiguous device slice owned by fault domain ``die``."""
        k = self.engines_per_die
        return tuple(self.devices[die * k:(die + 1) * k])

    def submesh(self, healthy: Sequence[int]) -> Mesh:
        """Flatten the healthy dies onto one ('stage','row','col') execution
        mesh: total stage depth = ``len(healthy) * stage``, per-stage grid
        geometry unchanged.  Pure placement — the flattened mesh drives the
        SAME staged dispatch path as a hand-built ``make_systolic_mesh``,
        and because every rung keeps the (rows, cols) grid, re-forming on
        fewer (or re-admitted) dies stays within one arithmetic class:
        chunk outputs are bit-equal across die counts."""
        healthy = sorted(healthy)
        assert healthy and all(0 <= d < self.dies for d in healthy), healthy
        devs = [d for die in healthy for d in self.die_devices(die)]
        from ..core.systolic import make_systolic_mesh
        return make_systolic_mesh(self.rows, self.cols,
                                  stage=len(healthy) * self.stage,
                                  devices=devs)

    def full_mesh(self) -> Mesh:
        """The explicit 4-axis ('die','stage','row','col') mesh — the model
        the die-aware admission rule (``core.systolic.
        seq_scaleout_admissible``) and perf model reason over.  Execution
        uses ``submesh`` (die and stage fold into one pipeline axis); this
        form keeps the fault-domain boundary explicit."""
        arr = np.array(list(self.devices)).reshape(
            self.dies, self.stage, self.rows, self.cols)
        return Mesh(arr, ('die', 'stage', 'row', 'col'),
                    axis_types=(AxisType.Auto,) * 4)


# name -> (dies, stage-per-die, rows, cols).  'graves-3x25' is the paper's
# 75-engine Table-2 topology refactored as THREE 25-engine dies: the
# degradation ladder then has real intermediate rungs (75 -> 50 -> 25
# engines) instead of jumping straight to single-host.  The small presets
# run on host devices (XLA_FLAGS=--xla_force_host_platform_device_count=N).
DIE_TOPOLOGIES = {
    'die-2x1x1': (2, 1, 1, 1),   # 2 dies of one engine each (2 devices)
    'die-3x1x1': (3, 1, 1, 1),   # 3 dies of one engine each (3 devices)
    'die-2x1x2': (2, 1, 1, 2),   # 2 dies of a 1x2 grid (4 devices)
    'graves-3x25': (3, 1, 5, 5),  # 3 dies of 5x5 = the Table-2 75 engines
}

_INSTALLED_DIE_MESH: Optional[DieMesh] = None


def make_die_topology(name: str, devices=None) -> DieMesh:
    """Build the named ``DIE_TOPOLOGIES`` preset as a ``DieMesh`` over the
    first ``dies * stage * rows * cols`` devices.  Pure topology
    construction — no placement happens until ``submesh`` is installed."""
    dies, stage, rows, cols = DIE_TOPOLOGIES[name]
    devices = list(jax.devices()) if devices is None else list(devices)
    need = dies * stage * rows * cols
    if len(devices) < need:
        raise ValueError(f'die topology {name!r} needs {need} devices, '
                         f'have {len(devices)}')
    return DieMesh(dies=dies, stage=stage, rows=rows, cols=cols,
                   devices=tuple(devices[:need]))


def install_die_topology(name: str, devices=None) -> DieMesh:
    """Build the named preset, register it as the process die-mesh model,
    and install its all-dies-healthy flattened submesh as the systolic
    execution mesh.  After installation the serving engine's recovery
    runtime (``runtime/recovery.py``) sees the die-level fault domains: an
    ``EngineFailure`` carrying a die id re-forms the mesh on the surviving
    dies (one ladder rung down) instead of abandoning the mesh, and a
    healed die is re-admitted by the canary-validated promotion path.
    Dispatch/placement only — numerics are unchanged on every rung."""
    global _INSTALLED_DIE_MESH
    dm = make_die_topology(name, devices)
    _INSTALLED_DIE_MESH = dm
    from ..core import systolic
    systolic.install_mesh(dm.submesh(range(dm.dies)))
    return dm


def current_die_mesh() -> Optional[DieMesh]:
    """The registered die-mesh model, or None (flat/no-mesh serving)."""
    return _INSTALLED_DIE_MESH


def clear_die_mesh() -> None:
    """Unregister the die-mesh model (the execution mesh is cleared
    separately via ``core.systolic.clear_mesh``)."""
    global _INSTALLED_DIE_MESH
    _INSTALLED_DIE_MESH = None


def resolve_rules(rules: Dict[str, object], mesh: Mesh) -> Dict[str, object]:
    """Drop mesh axes that don't exist on this mesh (e.g. 'pod' on single-pod)."""
    names = set(mesh.axis_names)
    out = {}
    for k, v in rules.items():
        if v is None:
            out[k] = None
        elif isinstance(v, str):
            out[k] = v if v in names else None
        else:
            kept = tuple(a for a in v if a in names)
            out[k] = kept if kept else None
    return out
