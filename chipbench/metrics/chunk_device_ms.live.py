"""Device milliseconds per step of the model chunk call: runs of the
engine's jitted chunk program (``jit_fwd``) in the traced window, over the
steps there."""

PROGRAM = 'jit_fwd'


def read(run):
    if run.trace is None:
        return None
    steps = len(run.trace.span_iv('step'))
    device_s = run.trace.module_s(PROGRAM)
    if not steps or device_s <= 0:
        return None
    return device_s / steps * 1e3
