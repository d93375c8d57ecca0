"""Reduction of a profiler trace (``.xplane.pb``) to busy, idle and
per-operation time on the device, and the host spans around them.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran, and their ``XLA Modules`` line one event
per program run.  Busy time is the union of the operation intervals.  The
harness's own spans (``chipbench.*``) sit on a host plane's lines.  Every
time here is in seconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

DEVICE_PREFIX = '/device:TPU:'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
SPAN_PREFIX = 'chipbench.'
WINDOW_SPAN = 'chipbench.window'


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into sorted disjoint ones."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind='stable')]
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    starts = iv[first, 0]
    ends = reach[np.concatenate([np.nonzero(first)[0][1:] - 1,
                                 [len(iv) - 1]])]
    return np.stack([starts, ends], axis=1)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Intervals cut to [lo, hi]; empty ones dropped."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the disjoint ``merged`` intervals cover."""
    iv = clip(merged, lo, hi)
    return float((iv[:, 1] - iv[:, 0]).sum())


@dataclasses.dataclass
class Device:
    """One device's events: op names with [start, end), module names with
    [start, end), and the union of the op intervals."""
    ops: List[str]
    op_iv: np.ndarray
    modules: List[str]
    module_iv: np.ndarray
    busy: np.ndarray


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Device]
    spans: Dict[str, np.ndarray]          # host span name -> (n, 2)

    def has_device(self) -> bool:
        """Whether any operation ran on a device in the trace."""
        return any(len(d.busy) for d in self.devices.values())

    def window(self) -> tuple:
        iv = self.spans.get(WINDOW_SPAN)
        if iv is None or len(iv) == 0:
            raise ValueError(f'no {WINDOW_SPAN} span in the trace')
        return float(iv[0, 0]), float(iv[-1, 1])

    def span_iv(self, name: str) -> np.ndarray:
        """``chipbench.<name>`` spans inside the window."""
        lo, hi = self.window()
        iv = self.spans.get(SPAN_PREFIX + name, np.zeros((0, 2)))
        return iv[(iv[:, 0] >= lo) & (iv[:, 1] <= hi)]

    def busy_s(self) -> float:
        """Seconds in the window with an operation on the device, averaged
        over the devices that ran any."""
        lo, hi = self.window()
        used = [d for d in self.devices.values() if len(d.busy)]
        if not used:
            return 0.0
        return sum(covered(d.busy, lo, hi) for d in used) / len(used)

    def busy_in(self, iv: np.ndarray) -> float:
        """Device-busy seconds inside the given host intervals, averaged
        over the devices used."""
        used = [d for d in self.devices.values() if len(d.busy)]
        if not used:
            return 0.0
        return sum(covered(d.busy, s, e) for d in used
                   for s, e in iv) / len(used)

    def module_s(self, prefix: str) -> float:
        """Seconds of runs of programs whose name starts with ``prefix``
        inside the window, summed over devices."""
        lo, hi = self.window()
        total = 0.0
        for d in self.devices.values():
            pick = np.array([m.startswith(prefix) for m in d.modules], bool)
            if pick.any():
                iv = clip(d.module_iv[pick], lo, hi)
                total += float((iv[:, 1] - iv[:, 0]).sum())
        return total

    def top_ops(self, n: int = 10) -> list:
        """[name, seconds] of the ``n`` operations with most device time in
        the window, summed over devices."""
        lo, hi = self.window()
        totals: Dict[str, float] = {}
        for d in self.devices.values():
            iv = np.clip(d.op_iv, lo, hi) if len(d.op_iv) else d.op_iv
            for name, (s, e) in zip(d.ops, iv):
                if e > s:
                    totals[name] = totals.get(name, 0.0) + float(e - s)
        return sorted(([k, v] for k, v in totals.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """[span, seconds] of the ``n`` longest stretches of the window
        with no operation on the first device used, each named after the
        harness span that overlaps it most (``none`` where no span does)."""
        lo, hi = self.window()
        used = [d for d in self.devices.values() if len(d.busy)]
        if not used:
            return [['none', hi - lo]]
        busy = clip(used[0].busy, lo, hi)
        edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind='stable')][:n]
        names = [k for k in self.spans if k != WINDOW_SPAN]
        out = []
        for s, e in gaps:
            best, who = 0.0, 'none'
            for k in names:
                ov = covered(union(self.spans[k]), s, e)
                if ov > best:
                    best, who = ov, k[len(SPAN_PREFIX):]
            out.append([who, float(e - s)])
        return out


def op_name(text: str) -> str:
    """``fusion.3`` from an XLA Ops event's ``%fusion.3 = f32[...] ...``."""
    return text.split(' = ', 1)[0].lstrip('%')


def _events(line):
    names, iv = [], []
    for ev in line.events:
        names.append(op_name(ev.name))
        iv.append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    return names, np.array(iv, float).reshape(-1, 2)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file with JAX's own reader."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, spans = {}, {}
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(DEVICE_PREFIX):
            ops, op_iv = (_events(lines[OPS_LINE]) if OPS_LINE in lines
                          else ([], np.zeros((0, 2))))
            mods, mod_iv = (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines
                            else ([], np.zeros((0, 2))))
            devices[plane.name] = Device(ops, op_iv, mods, mod_iv,
                                         union(op_iv))
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    spans = {k: np.array(sorted(v)) for k, v in spans.items()}
    return Trace(devices, spans)


def find_xplane(directory) -> Optional[str]:
    import glob
    found = sorted(glob.glob(f'{directory}/**/*.xplane.pb', recursive=True))
    return found[-1] if found else None
