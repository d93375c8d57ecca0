"""Split the host step of a traced benchmark run by the serving engine's
phases, from the program's own ``serving.*`` spans.

Runs one cell of the chip benchmark (``chipbench/run.py``) with its
arguments and, when the harness loads the run's trace, reduces the
``serving.*`` host events of that trace (``repro.serving.spans``) inside
the measured window to one JSON file:

- ``ms_per_step``: each phase's summed duration over the ``serving.step``
  spans in the window (``submit`` runs outside a step but is divided by the
  same count), and ``idle_ms_per_step``: the part of it with the device idle;
- ``admitted`` and ``admitting_steps``: the streams the admit spans
  counted, and how many of those spans admitted any;
- ``metrics``: ``admit_ms_per_stream`` (admit time over its ``streams``
  counters), ``admit_dispatches_per_step`` (the admit spans' ``dispatches``
  counters over the steps; None where no span carries that counter),
  ``launch_ms_per_step`` (pack + dispatch),
  ``deliver_ms_per_step`` (fetch + deliver), ``submit_ms_per_step``;
- ``coverage``: the share of ``serving.step`` time that the phase spans
  cover, and ``step_over_harness_step``: summed ``serving.step`` over summed
  ``chipbench.step`` time;
- ``idle_gaps``: the harness's ten longest device-idle gaps, each named
  ``<harness span>/<phase>`` after the phase span that overlaps it most;
- ``clock``: where each run of the chunk call (``jit_fwd`` on the device
  plane) falls against the host spans of the step that launched it
  (sync dispatch: the step's ``dispatch`` then its ``wait``).  Each run is
  matched to the ``dispatch`` span that starts nearest to it; a match error
  would show as an offset near a whole step period, or as a dispatch with
  no run or two.  ``inside`` counts runs that start after their dispatch
  begins and end before their wait ends, within ``TOL_S``; ``drift`` fits
  the start offset as a line in time, to tell a clock that drifts from
  latency that scatters.

Usage, from the root of a checkout (the trace is reduced, then deleted by
the harness as usual; the run's own result line is printed as usual):

    python3 scripts/serving_phases.py OUT.json -- --workload ctc421-c1.live \
        --seed 7 --seconds 51 --trace 1
"""
from __future__ import annotations

import json
import os
import sys
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, 'src')):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import trace as trace_mod  # noqa: E402
from chipbench.trace import clip, covered, union  # noqa: E402
from repro.serving.spans import serving_events  # noqa: E402

PHASES = ('admit', 'pack', 'dispatch', 'wait', 'fetch', 'deliver')
TOL_S = 50e-6
CHUNK_CALL = 'jit_fwd'
QUANTILES = (0, 1, 5, 25, 50, 75, 95, 99, 100)


def _iv(rows) -> np.ndarray:
    return np.array([(s, e) for s, e, _ in rows], float).reshape(-1, 2)


def in_window(events: dict, lo: float, hi: float) -> dict:
    """``events`` (``serving_events``' form) cut to spans inside [lo, hi]."""
    return {k: [r for r in rows if r[0] >= lo and r[1] <= hi]
            for k, rows in events.items()}


def _percentiles_us(v: np.ndarray) -> list:
    return ([float(x) for x in np.percentile(v * 1e6, QUANTILES)]
            if len(v) else [])


def clock(win: dict, device, lo: float, hi: float) -> dict:
    """Chunk-call runs on ``device`` against their step's dispatch and wait
    spans (see the module's docstring); offsets in microseconds."""
    disp, wait = _iv(win.get('dispatch', [])), _iv(win.get('wait', []))
    pick = np.array([m.startswith(CHUNK_CALL) for m in device.modules],
                    bool)
    mods = (device.module_iv[pick] if pick.any()
            else np.zeros((0, 2)))
    mods = mods[(mods[:, 0] >= lo) & (mods[:, 1] <= hi)]
    out = {'runs': len(mods), 'dispatches': len(disp), 'waits': len(wait)}
    if not (len(mods) and len(disp) and len(wait)):
        return out
    ds = disp[:, 0]
    right = np.clip(np.searchsorted(ds, mods[:, 0]), 1, len(ds) - 1)
    near = np.where(np.abs(mods[:, 0] - ds[right - 1])
                    <= np.abs(mods[:, 0] - ds[right]), right - 1, right)
    if len(ds) == 1:
        near = np.zeros(len(mods), int)
    own_wait = np.searchsorted(wait[:, 0], disp[near, 1], side='left')
    has_wait = own_wait < len(wait)
    start_off = mods[:, 0] - disp[near, 0]
    end_off = np.full(len(mods), np.inf)
    end_off[has_wait] = mods[has_wait, 1] - wait[own_wait[has_wait], 1]
    per_dispatch = np.bincount(near, minlength=len(disp))
    t = mods[:, 0] - lo
    slope, icept = np.polyfit(t, start_off, 1) if len(t) > 1 else (0.0, 0.0)
    resid = start_off - (slope * t + icept)
    tenth = max(1, len(t) // 10)
    out.update({
        'inside': int(((start_off >= -TOL_S) & (end_off <= TOL_S)).sum()),
        'start_before_dispatch': int((start_off < -TOL_S).sum()),
        'end_after_wait': int((end_off > TOL_S).sum()),
        'dispatches_with_runs': {str(k): int((per_dispatch == k).sum())
                                 for k in np.unique(per_dispatch)},
        'step_period_ms': float(np.median(np.diff(ds)) * 1e3)
        if len(ds) > 1 else None,
        'max_abs_start_offset_ms': float(np.abs(start_off).max() * 1e3),
        'quantiles': list(QUANTILES),
        'start_minus_dispatch_start_us': _percentiles_us(start_off),
        'start_minus_dispatch_end_us': _percentiles_us(
            mods[:, 0] - disp[near, 1]),
        'end_minus_wait_start_us': _percentiles_us(
            mods[has_wait, 1] - wait[own_wait[has_wait], 0]),
        'end_minus_wait_end_us': _percentiles_us(end_off[has_wait]),
        'run_us': _percentiles_us(mods[:, 1] - mods[:, 0]),
        'drift': {
            'slope_us_per_s': float(slope * 1e6),
            'offset_at_window_start_us': float(icept * 1e6),
            'residual_us': _percentiles_us(resid),
            'first_tenth_median_us': float(np.median(start_off[:tenth])
                                           * 1e6),
            'last_tenth_median_us': float(np.median(start_off[-tenth:])
                                          * 1e6),
        },
    })
    return out


def named_gaps(t, win: dict, lo: float, hi: float, busy: np.ndarray,
               n: int = 10) -> list:
    """The ``n`` longest device-idle gaps of the window as
    ``[<harness span>[/<phase>], seconds]``."""
    bz = clip(busy, lo, hi)
    edges = np.concatenate([[lo], bz.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind='stable')][:n]
    harness = {k[len(trace_mod.SPAN_PREFIX):]: union(v)
               for k, v in t.spans.items() if k != trace_mod.WINDOW_SPAN}
    phases = {p: union(_iv(win[p])) for p in PHASES + ('submit',)
              if win.get(p)}

    def most(spans, s, e):
        best, who = 0.0, None
        for k, u in spans.items():
            ov = covered(u, s, e)
            if ov > best:
                best, who = ov, k
        return who

    out = []
    for s, e in gaps:
        who, ph = most(harness, s, e) or 'none', most(phases, s, e)
        out.append([who + ('/' + ph if ph else ''), float(e - s)])
    return out


def reduce(events: dict, t) -> dict:
    """The phase split of the run whose chipbench trace is ``t`` and whose
    ``serving.*`` events are ``events``."""
    lo, hi = t.window()
    win = in_window(events, lo, hi)
    dur = {k: float(sum(e - s for s, e, _ in rows))
           for k, rows in win.items()}
    n = len(win.get('step', []))
    res = {'window_s': hi - lo, 'steps': n,
           'span_counts': {k: len(v) for k, v in win.items()},
           'total_s': dur}
    if not n:
        return res

    def per_step(*ks):
        return sum(dur.get(k, 0.0) for k in ks) / n * 1e3

    admits = win.get('admit', [])
    admitted = sum(c.get('streams', 0) for _, _, c in admits)
    res['admitted'] = admitted
    res['admitting_steps'] = sum(1 for _, _, c in admits
                                 if c.get('streams', 0))
    counted = [c['dispatches'] for _, _, c in admits if 'dispatches' in c]
    res['ms_per_step'] = {k: per_step(k)
                          for k in PHASES + ('submit', 'step')}
    res['metrics'] = {
        'admit_ms_per_stream': (dur.get('admit', 0.0) / admitted * 1e3
                                if admitted else None),
        'admit_dispatches_per_step': (sum(counted) / n if counted
                                      else None),
        'launch_ms_per_step': per_step('pack', 'dispatch'),
        'deliver_ms_per_step': per_step('fetch', 'deliver'),
        'submit_ms_per_step': per_step('submit'),
    }
    steps = _iv(win['step'])
    phase_u = union(np.concatenate([_iv(win[p]) for p in PHASES
                                    if win.get(p)] or [np.zeros((0, 2))]))
    res['coverage'] = (sum(covered(phase_u, s, e) for s, e in steps)
                       / dur['step'])
    hs = t.span_iv('step')
    res['harness_steps'] = len(hs)
    res['step_over_harness_step'] = (
        dur['step'] / float((hs[:, 1] - hs[:, 0]).sum()) if len(hs) else None)
    used = [d for d in t.devices.values() if len(d.busy)]
    if used:
        busy = used[0].busy
        res['idle_ms_per_step'] = {
            k: (dur[k] - sum(covered(busy, s, e) for s, e in _iv(win[k])))
            / n * 1e3 for k in PHASES + ('submit',) if win.get(k)}
        res['idle_gaps'] = named_gaps(t, win, lo, hi, busy)
        res['clock'] = clock(win, used[0], lo, hi)
    return res


def main(argv) -> int:
    if len(argv) < 2 or argv[1] != '--':
        print(__doc__, file=sys.stderr)
        return 2
    out_path, run_args = argv[0], argv[2:]
    load = trace_mod.load

    def load_and_reduce(path):
        t = load(path)
        try:
            res = reduce(serving_events(path), t)
        except Exception:  # the run goes on; its fault is in the file
            res = {'error': traceback.format_exc()}
        with open(out_path, 'w') as f:
            json.dump(res, f, indent=1)
        return t

    trace_mod.load = load_and_reduce
    from chipbench import run
    return run.main(run_args)


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
