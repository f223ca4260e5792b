"""viterbi_unified_roofline: the unified Viterbi kernel's share of its
HBM roofline: the bytes it must move for the window's launched frames
(``roofline.unified_kernel_bytes``) at the chip's published HBM
bandwidth, over its device time summed over the chips. The bound is
memory: no vector-unit peak is published for the chip."""
from harness import roofline


def read(run):
    if run.trace is None:
        return None
    t = run.trace["kernel_s"].get("viterbi_unified", 0.0)
    frames = run.delta["frames"]
    if not t or not frames:
        return None
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * roofline.unified_kernel_bytes(frames, run.cfg) / bw / t
