"""Engine host step (``StreamingEngine.step``: admit, pack, launch, commit,
decode) in host milliseconds per step; see ``_host_step.py``."""
from chipbench.metrics._host_step import host_ms_per_step


def read(run):
    return host_ms_per_step(run)
