"""95th percentile, over every chunk emission in the window, of the time
from the arrival of the chunk's last frame to the end of the ``step()``
that committed its log-probs and CTC symbols (host clock)."""
import numpy as np


def read(run):
    if not len(run.latencies_s):
        return None
    return float(np.percentile(run.latencies_s, 95) * 1e3)
