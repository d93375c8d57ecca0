"""Median of the chunk-emission latencies that ``emit_p95_ms`` reads."""
import numpy as np


def read(run):
    if not len(run.latencies_s):
        return None
    return float(np.percentile(run.latencies_s, 50) * 1e3)
