"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout's root
lists the cells and metrics; each configuration is the file its entry
names, each traffic mix is ``traffic/<name>.json``, each metric is read by
``metrics/<name>.py``, each reference is ``reference/<name>.py`` and each
model kind (a configuration's ``model.kind``) is ``models/<kind>.py``.
Adding any of them takes new files and entries, never an edit here."""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


class Spec:
    """``BENCHMARK.json`` and the files it leads to."""

    def __init__(self, root: pathlib.Path, bench_dir: pathlib.Path = HERE):
        self.root = pathlib.Path(root)
        self.dir = pathlib.Path(bench_dir)
        self.data = json.loads((self.root / 'BENCHMARK.json').read_text())

    def cell(self, name: str) -> dict:
        for cell in self.data['workloads']:
            if cell['name'] == name:
                return cell
        raise KeyError(f'no workload {name!r} in BENCHMARK.json')

    def config(self, cell: dict) -> dict:
        for cfg in self.data['configs']:
            if cfg['name'] == cell['config']:
                return json.loads((self.root / cfg['file']).read_text())
        raise KeyError(f'no configuration {cell["config"]!r}')

    def traffic(self, cell: dict) -> dict:
        return json.loads(
            (self.dir / 'traffic' / f'{cell["traffic"]}.json').read_text())

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / 'peaks.json').read_text())
        if device_kind not in table:
            raise KeyError(f'no peaks for device kind {device_kind!r} in '
                           'peaks.json')
        return table[device_kind]

    def metrics(self, cell: dict, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        those listing it, and those with no list whose end-to-end metric
        (``moves``, for a per-layer one) the cell reports."""
        e2e = {m['name'] for m in self._listed('end_to_end', cell)}
        out = []
        for m in self.data[kind]:
            if 'workloads' in m:
                if cell['name'] in m['workloads']:
                    out.append(m)
            elif kind == 'end_to_end' or m['moves'] in e2e:
                out.append(m)
        return out

    def _listed(self, kind: str, cell: dict) -> list:
        return [m for m in self.data[kind]
                if cell['name'] in m.get('workloads', [cell['name']])]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``metrics/<metric>.py``."""
        return load_module(self.dir / 'metrics' / f'{metric}.py').read

    def reference(self, name: str):
        return load_module(self.dir / 'reference' / f'{name}.py')

    def model(self, config: dict):
        """The module of the configuration's model kind: its
        ``make_weights(model, seed)``, ``program(config, weights)`` (the
        program's architecture and parameters), ``outputs(frames)`` (output
        rows of a stream of ``frames``), ``flops_per_frame(model)`` and
        ``chunk_bytes(model, frames, streams, itemsize)``."""
        kind = config['model']['kind']
        return load_module(self.dir / 'models' / f'{kind}.py')


def load_module(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        'chipbench_' + path.stem.replace('.', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
