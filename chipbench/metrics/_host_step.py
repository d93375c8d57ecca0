"""Host milliseconds per engine step: each ``chipbench.step`` span in the
traced window minus the device-busy time inside it, summed, over steps."""


def host_ms_per_step(run):
    if run.trace is None:
        return None
    steps = run.trace.span_iv('step')
    if not len(steps):
        return None
    wall = float((steps[:, 1] - steps[:, 0]).sum())
    return (wall - run.trace.busy_in(steps)) / len(steps) * 1e3
