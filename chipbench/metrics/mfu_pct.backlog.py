"""The whole step's share of the chip's bf16 peak: the model FLOPs of the
frames committed in the traced window, over the window's length times the
peak of the device kind.  The FLOPs a frame are the model kind's
(``models/<kind>.py``)."""


def read(run):
    if run.trace is None or not run.step_frames.sum():
        return None
    lo, hi = run.trace.window()
    flops = float(run.step_frames.sum()) * run.kind.flops_per_frame(run.model)
    return flops / ((hi - lo) * run.peaks['bf16_flops_per_s']) * 100
