"""window_p50_ms: the median of the samples of window_p99_ms."""
import numpy as np


def read(run):
    if not run.lat_ms.size:
        return None
    return float(np.percentile(run.lat_ms, 50))
