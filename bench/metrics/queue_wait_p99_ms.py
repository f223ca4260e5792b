"""queue_wait_p99_ms: the 99th percentile of the server's
``queue_wait_ms`` histogram (enqueue to batch take) over the window:
the histogram's bucket counts at the window's end less those at its
start, interpolated inside the bucket."""


def read(run):
    h = run.delta["stages"]["queue_wait_ms"]
    n = sum(h["counts"])
    if not n:
        return None
    target, cum = 0.99 * n, 0
    bounds = h["bounds"]
    for i, c in enumerate(h["counts"]):
        if c and cum + c >= target:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            return lo + (target - cum) / c * max(0.0, hi - lo)
        cum += c
    return None
