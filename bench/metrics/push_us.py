"""push_us: mean host microseconds of one ``DecodeServer.push`` call in
the window (admission, validation and sanitizing, depuncturing, framing
of completed windows), timed by the benchmark around the call."""


def read(run):
    if not run.push_us.size:
        return None
    return float(run.push_us.mean())
