"""Share of the traced window with no operation running on the device."""


def read(run):
    if run.trace is None or not run.trace.has_device():
        return None
    lo, hi = run.trace.window()
    return (1 - run.trace.busy_s() / (hi - lo)) * 100
