"""dispatch_ms: mean host milliseconds per launch of the server's batch
pack (concatenating the windows) plus its launch (host to device copy,
dispatch, retry bookkeeping), from the ``batch_pack_ms`` and
``launch_ms`` stage histograms' sums over the window."""


def read(run):
    st = run.delta["stages"]
    n = st["launch_ms"]["count"]
    if not n:
        return None
    return (st["batch_pack_ms"]["total"] + st["launch_ms"]["total"]) / n
