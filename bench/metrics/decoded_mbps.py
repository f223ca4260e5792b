"""decoded_mbps: every bit returned to the client by ``poll`` in the
measured window, over the window's length (host clock)."""


def read(run):
    return run.bits_in_window / run.window_s / 1e6
