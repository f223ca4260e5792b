"""gen_lag_p99_ms: open loop only; the 99th percentile of how late the
generator started each push of the window after its due time (host
clock). A starved generator shows here, not as a fast server."""
import numpy as np


def read(run):
    if not run.lag_ms.size:
        return None
    return float(np.percentile(run.lag_ms, 99))
