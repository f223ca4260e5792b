"""window_p99_ms: the 99th percentile, over every window completed by a
push due in the measured window, of the time from that push's due time
to the return of the poll that handed back the window's bits (host
clock)."""
import numpy as np


def read(run):
    if not run.lat_ms.size:
        return None
    return float(np.percentile(run.lat_ms, 99))
