"""The system under test, built from a configuration and a traffic mix:
one ``DecodeServer`` with one session per link, on the cell's devices.
The only module of the harness that imports the program."""
from __future__ import annotations

from . import codes
from .traffic import Traffic


def use_compile_cache() -> str:
    """The program's persistent compilation cache (its fixed place in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), holding every program
    however small or quick to compile: the program's own setting keeps
    only those that take a second or more, and a cell's open loop has
    dozens of small ones."""
    import jax
    from repro.compile_cache import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build(cfg: dict, traffic: Traffic, devices):
    """(server, session ids, the server's Backpressure exception)."""
    from repro.core import DecoderConfig, FrameSpec, make_trellis
    from repro.serve import Backpressure, DecodeServer
    k, polys = codes.generators(cfg)
    dcfg = DecoderConfig(trellis=make_trellis(k, tuple(polys)),
                         spec=FrameSpec(**cfg["frame"]),
                         rate=cfg["code"]["rate"])
    mesh = None
    if traffic.mesh > 1:
        from repro.distributed.stream import frame_mesh
        if len(devices) < traffic.mesh:
            raise ValueError(f"mesh of {traffic.mesh} devices, "
                             f"{len(devices)} given")
        mesh = frame_mesh(list(devices)[:traffic.mesh])
    srv = DecodeServer(slots=traffic.slots, max_sessions=traffic.links,
                       mesh=mesh)
    sids = [srv.open_session(dcfg, chunk_frames=traffic.chunk_frames)
            for _ in range(traffic.links)]
    return srv, sids, Backpressure


def counters(srv) -> dict:
    """The server's counters that the per-layer metrics and the check
    read: launch and frame totals, fault totals, the stage histograms'
    bucket counts and sums, and the plan cache's compile count."""
    totals = srv.metrics.totals()
    stages = {}
    for name in ("queue_wait_ms", "batch_pack_ms", "launch_ms"):
        h = srv.metrics.stage(name)
        stages[name] = {"counts": list(h.counts), "bounds": list(h.bounds),
                        "count": h.count, "total": h.total}
    keys = ("launches", "windows", "frames", "pad_frames", "bits",
            "launch_errors", "timeouts", "retries", "degraded",
            "breaker_trips", "evacuated", "poisoned_pushes", "quarantined")
    out = {k: totals[k] for k in keys}
    out["stages"] = stages
    out["plan_traces"] = srv.cache.stats()["traces"]
    return out
