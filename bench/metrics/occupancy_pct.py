"""occupancy_pct: how full the window's launches were: live frames
decoded over launches times the server's batch of slots x chunk_frames
frames (before the kernel's tile padding)."""


def read(run):
    d = run.delta
    if not d["launches"]:
        return None
    cap = d["launches"] * run.traffic.slots * run.traffic.chunk_frames
    return 100.0 * d["frames"] / cap
