"""Frames committed in the window, partial utterances included, over the
window's length (host clock)."""


def read(run):
    if not run.window.steps:
        return None
    return float(run.step_frames.sum() / run.window.seconds)
