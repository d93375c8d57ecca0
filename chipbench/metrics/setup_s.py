"""Seconds from process start to the window's opening: imports, device
start, weights, the first compile or cache load, and the warm-up on the
mix's own traffic."""


def read(run):
    return float(run.setup_s)
