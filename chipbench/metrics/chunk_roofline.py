"""The chunk call's share of its roofline: the least time the chip could
take for the useful work of the window's steps (valid frames of active
streams, counted by the model kind; see ``chipbench/flops.py``) over the
device time of the engine's jitted chunk program (``jit_fwd``).  It reads
the same work whichever backend implements the call."""
from chipbench.flops import chunk_least_s

PROGRAM = 'jit_fwd'


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.module_s(PROGRAM)
    if device_s <= 0 or not run.step_frames.sum():
        return None
    least = sum(chunk_least_s(run.kind, run.model, int(f), int(s), run.peaks)
                for f, s in zip(run.step_frames, run.step_streams))
    return least / device_s * 100
