"""kernel_ns_per_bit: device time of the unified Viterbi kernel in the
traced window, summed over the chips, over the decoded bits the window's
launches carried (frames launched x f, before tile padding)."""


def read(run):
    if run.trace is None:
        return None
    ns = run.trace["kernel_s"].get("viterbi_unified", 0.0) * 1e9
    bits = run.delta["frames"] * run.cfg["frame"]["f"]
    if not ns or not bits:
        return None
    return ns / bits
