"""Paper Tables IV & V: decoder throughput, regular vs parallel traceback.

Without an accelerator the wall-clock numbers are CPU (jitted XLA) and
meaningful only as RELATIVE comparisons between the paper's own variants:
serial vs parallel traceback (Table IV vs V: paper sees ~2x). Chip
numbers come from ``bench/run.py``.
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import FrameSpec, STD_K7, framed_decode
from repro.core.framed import frame_llr
from repro.kernels import ops

#: Compiled-mode switch (``--compiled`` / bench_gate's BENCH_COMPILED):
#: False runs the Pallas kernels under the interpreter (the only option
#: on CPU), True compiles them for the real backend — the sections
#: themselves are identical, only ``interpret=`` changes, and the
#: platform stamp on the recorded run keeps the two trajectories apart.
COMPILED = False


def set_compiled(on: bool = True) -> None:
    global COMPILED
    COMPILED = bool(on)


def _interpret() -> bool:
    """interpret= for every kernel launch in this module."""
    return not COMPILED


def _time(fn, *args, reps=3):
    fn(*args).block_until_ready()              # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps


def _time_best(fn, *args, reps=3):
    """Min-of-reps: robust to the cgroup scheduling stalls of shared CPUs
    (a single stall poisons a mean but not a min)."""
    fn(*args).block_until_ready()              # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def throughput_framed(spec: FrameSpec, n: int = 2_000_000) -> dict:
    """Mb/s of the jitted framed decoder (pure-JAX path, compiled)."""
    rng = np.random.default_rng(0)
    llr = jnp.asarray(rng.standard_normal((n, 2)).astype(np.float32))
    fn = jax.jit(lambda l: framed_decode(l, STD_K7, spec))
    dt = _time(fn, llr)
    return {"us_per_call": dt * 1e6, "mbps": n / dt / 1e6}


def table4(n=1_000_000):
    rows = []
    for v2 in (10, 20, 40):
        for f in (64, 256):
            r = throughput_framed(FrameSpec(f=f, v1=20, v2=v2), n)
            rows.append({"table": "IV", "f": f, "v2": v2, **r})
    return rows


def table5(n=1_000_000):
    rows = []
    for v2 in (25, 45):
        for f0 in (8, 32):
            spec = FrameSpec(f=256, v1=20, v2=v2, f0=f0, v2s=v2)
            r = throughput_framed(spec, n)
            rows.append({"table": "V", "f0": f0, "v2": v2, **r})
    return rows


def kernel_sweep(full: bool = False):
    """Packed x radix x tile-size sweep of the unified kernel.

    The perf-trajectory benchmark for the kernel's survivor compression
    (BENCH_kernels.json). The (pack=False, radix=2, ft=8) row is the seed
    kernel; (pack=True, radix=4, ft='auto') is the served configuration.
    ``vmem_kib`` is the tile's footprint under Mosaic padding. Interpret
    mode => relative numbers.
    """
    rng = np.random.default_rng(0)
    spec = FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45)
    n = (128 if full else 32) * spec.f
    llr = jnp.asarray(rng.standard_normal((n, 2)).astype(np.float32))
    frames = frame_llr(llr, spec)

    from repro.kernels.autotune import launch_tile, tile_ok
    grid = [(False, 2, 8),                          # seed configuration
            (False, 4, 8),                          # one knob at a time
            (True, 2, 8),
            (True, 4, 8),
            (False, 2, 32),                         # deeper tiles
            (True, 4, 32),
            (True, 4, "auto")]                      # the served plan
    if COMPILED:
        # tiles Mosaic accepts (kernels/viterbi_unified.check_compiled)
        grid = [g for g in grid
                if g[2] == "auto" or tile_ok(g[2], frames.shape[0])]
    rows = []
    for pack, radix, ft in grid:
        fn = jax.jit(lambda fr, p=pack, r=radix, t=ft:
                     ops.viterbi_decode_frames(
                         fr, STD_K7, spec, frames_per_tile=t,
                         pack_survivors=p, radix=r, interpret=_interpret()))
        dt = _time_best(fn, frames, reps=3)
        tile = launch_tile(STD_K7, spec, frames.shape[0],
                           frames_per_tile=ft, pack_survivors=pack)
        rows.append({"table": "kernels", "pack": pack, "radix": radix,
                     "ft": tile.frames_per_tile, "auto": ft == "auto",
                     "n_bits": n, "reps": 3,
                     "vmem_kib": round(tile.vmem_bytes / 1024, 1),
                     "us_per_call": dt * 1e6, "mbps": n / dt / 1e6})
    return rows


def streaming_bench(full: bool = False):
    """Streaming front-end vs single-shot decode on a multi-chunk stream.

    Both run the compiled reference backend (the kernel backends interpret
    on CPU, which would time the interpreter, not the pipeline), and both
    are timed on the same numpy-in -> numpy-out contract a receiver sees
    (the single shot pays its host<->device staging too). The streaming
    rows include all host-side chunking/framing plus the flush, so beating
    single-shot means the double-buffered dispatch more than hides the
    chunk bookkeeping (acceptance: streaming >= single-shot here).
    """
    from repro.core import DecoderConfig, make_decoder
    from repro.core.stream import make_stream_decoder
    rng = np.random.default_rng(0)
    spec = FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45)
    nframes = 512 if full else 128
    n = nframes * spec.f
    llr = rng.standard_normal((n, 2)).astype(np.float32)
    cfg = DecoderConfig(spec=spec)
    rows = []

    dec = make_decoder(cfg)
    np.asarray(dec(llr, n))                            # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(dec(llr, n))
        best = min(best, time.perf_counter() - t0)
    rows.append({"table": "streaming", "variant": "single_shot",
                 "n_bits": n, "chunk_frames": nframes, "reps": 3,
                 "us_per_call": best * 1e6, "mbps": n / best / 1e6})

    for chunk in (16, 32):
        sdec = make_stream_decoder(cfg, chunk_frames=chunk)

        def run_stream():
            out = [sdec.push(llr[i:i + chunk * spec.f])
                   for i in range(0, n, chunk * spec.f)]
            out.append(sdec.flush())
            return sum(o.size for o in out)

        assert run_stream() == n                   # warm every chunk shape
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            nbits = run_stream()
            best = min(best, time.perf_counter() - t0)
            assert nbits == n
        rows.append({"table": "streaming",
                     "variant": f"stream_chunk{chunk}", "n_bits": n,
                     "chunk_frames": chunk, "reps": 3,
                     "us_per_call": best * 1e6, "mbps": n / best / 1e6})
    return rows


def _serve_workload(full: bool):
    """Session mix + pre-cut raw chunk streams shared by serve_bench and
    serve_faults_bench: 8 (full: 16) sessions across three code configs —
    K=7 rate-1/2, K=7 rate-3/4 (raw punctured push), K=5 rate-1/2 —
    pushing one chunk per session per round. Returns
    (streams, total_bits, nbuckets, C, nchunks, nsess) where streams is
    [(cfg, [chunk0, chunk1, ...], n_bits), ...]."""
    from repro.core import DecoderConfig
    from repro.core.puncture import pattern
    from repro.core.trellis import make_trellis

    C = 16                                     # chunk frames per session
    nchunks = 24 if full else 6
    nsess = 16 if full else 8
    k5 = make_trellis(5, (0o23, 0o35))
    spec12 = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
    spec34 = FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21)
    cfgs = [DecoderConfig(spec=spec12),                   # K7 1/2
            DecoderConfig(spec=spec34, rate="3/4"),       # K7 punctured
            DecoderConfig(trellis=k5, spec=spec12)]       # K5 1/2
    # half the sessions on the main code, the rest split across the other
    # two — every bucket sees real batching (4/2/2 at nsess=8)
    mix = ([cfgs[0]] * (nsess // 2) + [cfgs[1]] * (nsess // 4)
           + [cfgs[2]] * (nsess - nsess // 2 - nsess // 4))

    rng = np.random.default_rng(0)
    streams = []                               # (cfg, raw chunks, n_bits)
    for cfg in mix:
        n = C * cfg.spec.f * nchunks           # stages == bits
        if cfg.punctured:
            pat = pattern(cfg.rate)
            m = n * pat.sum() // pat.shape[1]  # raw punctured symbols
            raw = rng.standard_normal(m).astype(np.float32)
            per = m // nchunks
        else:
            raw = rng.standard_normal((n, 2)).astype(np.float32)
            per = n // nchunks
        streams.append((cfg, [raw[i * per:(i + 1) * per]
                              for i in range(nchunks)], n))
    total_bits = sum(n for _, _, n in streams)
    nbuckets = len({(cfg.trellis, cfg.spec) for cfg, _, _ in streams})
    return streams, total_bits, nbuckets, C, nchunks, nsess


def serve_bench(full: bool = False):
    """Multi-tenant serve trajectory: sessions x codes sweep.

    The _serve_workload mix decoded (a) by N independent StreamDecoders
    and (b) by one DecodeServer batching each bucket's windows into
    single launches. Both run the compiled reference backend on identical
    arrival patterns (one chunk per session per round), so the delta is
    purely dispatch aggregation: the server wins when one
    (slots*C)-frame launch beats `slots` C-frame launches. Aggregate
    Mb/s is total decoded bits over wall time; the server rows carry the
    per-bucket latency/occupancy metrics and the plan-cache trace count
    (the serve acceptance criterion: server >= independent, one compile
    per bucket shape).
    """
    from repro.core import make_stream_decoder
    from repro.serve import DecodeServer, PlanCache

    streams, total_bits, nbuckets, C, nchunks, nsess = _serve_workload(full)

    def run_independent():
        decs = [make_stream_decoder(cfg, chunk_frames=C)
                for cfg, _, _ in streams]
        got = 0
        for r in range(nchunks):
            for dec, (_, chunks, _) in zip(decs, streams):
                got += dec.push(chunks[r]).size
        for dec in decs:
            got += dec.flush().size
        return got

    cache = PlanCache()

    def run_server():
        srv = DecodeServer(slots=4, max_sessions=2 * nsess, cache=cache)
        sids = [srv.open_session(cfg, chunk_frames=C)
                for cfg, _, _ in streams]
        got = 0
        for r in range(nchunks):
            for sid, (_, chunks, _) in zip(sids, streams):
                srv.push(sid, chunks[r])
            while srv.step():                  # drain queues, stay async
                pass
            for sid in sids:
                got += srv.poll(sid).size      # non-blocking collect
        for sid in sids:
            got += srv.close_session(sid).size
        return got, srv

    rows = []
    assert run_independent() >= total_bits     # warm every chunk shape
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        nbits = run_independent()
        best = min(best, time.perf_counter() - t0)
    rows.append({"table": "serve", "variant": "independent",
                 "sessions": nsess, "codes": 3, "buckets": nbuckets,
                 "chunk_frames": C, "n_bits": total_bits, "reps": 3,
                 "us_per_call": best * 1e6, "mbps": total_bits / best / 1e6})

    nbits, _ = run_server()                    # warm (and count compiles)
    assert nbits >= total_bits
    best, srv = float("inf"), None
    for _ in range(3):
        t0 = time.perf_counter()
        nbits, srv = run_server()
        best = min(best, time.perf_counter() - t0)
        assert nbits >= total_bits
    tot = srv.metrics.totals()
    rows.append({"table": "serve", "variant": "server",
                 "sessions": nsess, "codes": 3, "buckets": nbuckets,
                 "chunk_frames": C, "slots": 4, "n_bits": total_bits,
                 "reps": 3, "us_per_call": best * 1e6,
                 "mbps": total_bits / best / 1e6,
                 "p50_ms": round(tot["p50_ms"], 3),
                 "p99_ms": round(tot["p99_ms"], 3),
                 "occupancy": round(tot["occupancy"], 4),
                 "launches": tot["launches"],
                 "plan_traces": cache.stats()["traces"]})
    return rows


def serve_faults_bench(full: bool = False):
    """Serve throughput under injected launch faults (the
    'serve_under_faults' trajectory section).

    Same workload and server geometry as serve_bench's "server" variant,
    with a seeded FaultInjector raising a kernel exception on 1% of
    launches plus every 16th deterministically (the `every` term
    guarantees the retry path actually runs in the quick CI workload,
    where 1% of ~20 launches would usually round to zero). Every failed
    launch is retried with zero backoff on the warm plan cache, so the
    row measures the price of fault recovery itself: dispatch + failed
    attempt + redispatch. The run must still deliver every bit. The
    regression gate tracks this row's mbps like the clean serve row.
    """
    from repro.serve import DecodeServer, PlanCache
    from repro.testing import FaultInjector, FaultSpec

    streams, total_bits, nbuckets, C, nchunks, nsess = _serve_workload(full)
    cache = PlanCache()

    def run_server(faults):
        srv = DecodeServer(slots=4, max_sessions=2 * nsess, cache=cache,
                           max_retries=3, backoff_s=0.0, faults=faults)
        sids = [srv.open_session(cfg, chunk_frames=C)
                for cfg, _, _ in streams]
        got = 0
        for r in range(nchunks):
            for sid, (_, chunks, _) in zip(sids, streams):
                srv.push(sid, chunks[r])
            while srv.step():
                pass
            for sid in sids:
                got += srv.poll(sid).size
        for sid in sids:
            got += srv.close_session(sid).size
        return got, srv

    nbits, _ = run_server(None)                # warm/compile fault-free
    assert nbits >= total_bits
    best, srv, inj = float("inf"), None, None
    for _ in range(3):
        # fresh injector, same seed: identical fault schedule every rep
        # (and every PR), so the mbps trajectory is comparable
        faults = FaultInjector(
            FaultSpec("launch_error", p=0.01, every=16), seed=11)
        t0 = time.perf_counter()
        nbits, this_srv = run_server(faults)
        dt = time.perf_counter() - t0
        assert nbits >= total_bits             # full recovery, always
        if dt < best:
            best, srv, inj = dt, this_srv, faults
    tot = srv.metrics.totals()
    assert tot["launch_errors"] == inj.injected["launch_error"]
    return [{"table": "serve_faults", "variant": "server_faults",
             "sessions": nsess, "codes": 3, "buckets": nbuckets,
             "chunk_frames": C, "slots": 4, "n_bits": total_bits,
             "reps": 3, "us_per_call": best * 1e6,
             "mbps": total_bits / best / 1e6,
             "injected": int(inj.injected["launch_error"]),
             "launch_errors": tot["launch_errors"],
             "retries": tot["retries"], "degraded": tot["degraded"],
             "p99_ms": round(tot["p99_ms"], 3),
             "health": tot["health"]}]


def block_bench(full: bool = False):
    """Intra-frame block-parallel decode vs the sequential single-scan
    plan on a FEW-long-frames workload (the 'block' trajectory section).

    A handful of f=4096 frames — the latency scenario block mode exists
    for (one long serve window, not a deep batch) — decoded by the same
    unified kernel twice under the same VMEM budget. The sequential
    variant scans all v1+f+v2 stages per grid step and cannot fill even
    the minimum 8-frame tile, so most of its per-step width is padding;
    the blocked variant lets resolve_block split each frame into ~32
    blocks of f/B + 2*overlap stages laid out on the frame axis, which
    fill a wide tile exactly — the tentpole mechanism ("a single long
    frame fills a tile the way many short frames do"). Interpret mode =>
    relative numbers; the acceptance criterion (blocked >= 1.5x
    sequential at L >= 4096, equal VMEM budget) is asserted here so the
    trajectory can never silently record a regressed decomposition.
    """
    from repro.kernels.block import resolve_block
    rng = np.random.default_rng(0)
    spec = FrameSpec(f=4096, v1=32, v2=32, f0=32, v2s=32)
    nframes = 4 if full else 2
    n = nframes * spec.f
    llr = jnp.asarray(rng.standard_normal((n, 2)).astype(np.float32))
    frames = frame_llr(llr, spec)
    bf, ov = resolve_block(STD_K7, spec, "auto", None)
    assert bf > 1, "auto policy must engage at f=4096"

    rows = []
    by_variant = {}
    for variant, B, o in (("sequential", 1, 0), ("blocked", bf, ov)):
        fn = jax.jit(lambda fr, B=B, o=o: ops.viterbi_decode_frames(
            fr, STD_K7, spec, frames_per_tile="auto",
            block_frames=B, overlap=o, interpret=_interpret()))
        dt = _time_best(fn, frames, reps=2)
        mbps = n / dt / 1e6
        by_variant[variant] = mbps
        rows.append({"table": "block", "variant": variant, "f": spec.f,
                     "block_frames": B, "overlap": o, "n_bits": n,
                     "reps": 2, "us_per_call": dt * 1e6, "mbps": mbps})
    ratio = by_variant["blocked"] / by_variant["sequential"]
    if not COMPILED:
        # the interpret-mode win comes from tile fill; on real hardware
        # the blocked-vs-sequential trade-off is exactly what the compiled
        # trajectory exists to MEASURE (ROADMAP item 1 follow-on), so the
        # ratio is recorded there, not asserted
        assert ratio >= 1.5, (
            f"acceptance criterion failed: block-parallel decode is only "
            f"{ratio:.2f}x the sequential-scan plan at f={spec.f} (needs "
            f">= 1.5x at equal VMEM budget)")
    return rows


#: Offered-load levels of the serve_load section. Fixed: the regression
#: gate compares stored p99s per level, so the levels are part of the
#: trajectory contract (ROADMAP item 4's "p99 vs offered load at
#: 64/256/1024 sessions").
LOAD_LEVELS = (64, 256, 1024)


def serve_load_sweep(full: bool = False):
    """Tail-latency-under-load SLO curves (the 'serve_load' section).

    One code config, ``LOAD_LEVELS`` sessions each pushing one C-frame
    chunk per round against a fixed-capacity server (16 slots), so rising
    session count IS rising offered load: at 64 sessions a round drains
    in 4 launches, at 1024 it takes 64 and late windows queue behind
    early ones. Each level records p50/p99 queue-wait (the PR 7
    ``queue_wait_ms`` stage histogram — time from push to batch-pack) and
    p50/p99 end-to-end window latency (push to materialized bits) from a
    fresh server per rep; of ``reps`` runs the one with the LOWEST p99 is
    kept — the min-of-reps discipline applied to a latency metric, since
    scheduler stalls on a shared runner only ever inflate the tail. The
    plan cache is shared across levels and reps (the batch shape
    ``slots x C`` frames never changes), so rep 1 is the only compile.

    The regression gate enforces these rows INVERTED vs the throughput
    sections: p99 above (1 + tol) x the best stored comparable p99
    fails the gate.
    """
    from repro.core import DecoderConfig
    from repro.serve import DecodeServer, PlanCache

    C = 2                                      # chunk frames per push
    spec = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
    cfg = DecoderConfig(spec=spec)
    rounds = 4 if full else 2
    reps = 2
    slots = 16
    cache = PlanCache()
    rng = np.random.default_rng(0)
    chunk = rng.standard_normal((C * spec.f, 2)).astype(np.float32)

    rows = []
    for nsess in LOAD_LEVELS:
        total_bits = nsess * rounds * C * spec.f

        def run(nsess=nsess):
            srv = DecodeServer(slots=slots, max_sessions=nsess,
                               cache=cache)
            sids = [srv.open_session(cfg, chunk_frames=C)
                    for _ in range(nsess)]
            got = 0
            for _ in range(rounds):
                for sid in sids:
                    srv.push(sid, chunk)
                while srv.step():
                    pass
                for sid in sids:
                    got += srv.poll(sid).size
            for sid in sids:
                got += srv.close_session(sid).size
            return got, srv

        nbits, _ = run()                       # warm the shared plan cache
        assert nbits == total_bits
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            nbits, srv = run()
            dt = time.perf_counter() - t0
            assert nbits == total_bits
            tot = srv.metrics.totals()
            if best is None or tot["p99_ms"] < best[0]:
                qw = srv.metrics.stage("queue_wait_ms")
                best = (tot["p99_ms"], dt, tot,
                        (qw.percentile(50), qw.percentile(99)))
        _, dt, tot, (q50, q99) = best
        rows.append({"table": "serve_load", "variant": f"sessions{nsess}",
                     "sessions": nsess, "slots": slots, "chunk_frames": C,
                     "rounds": rounds, "n_bits": total_bits, "reps": reps,
                     "mbps": total_bits / dt / 1e6,
                     "queue_p50_ms": round(q50, 3),
                     "queue_p99_ms": round(q99, 3),
                     "p50_ms": round(tot["p50_ms"], 3),
                     "p99_ms": round(tot["p99_ms"], 3),
                     "launches": tot["launches"],
                     "occupancy": round(tot["occupancy"], 4)})
    return rows


def plan_rows():
    """The kernel's tile plans at the default 2 MiB budget, packed and
    unpacked survivors, under Mosaic padding — the BENCH_kernels.json
    record of what the planner chooses."""
    from repro.kernels.autotune import plan_tiles
    spec = FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45)
    rows = []
    for name, pack in (("packed", True), ("unpacked", False)):
        p = plan_tiles(STD_K7, spec, pack_survivors=pack)
        rows.append({"table": "plans", "plan": name,
                     "ft": p.frames_per_tile,
                     "vmem_kib": round(p.vmem_bytes / 1024, 1),
                     "budget_kib": round(p.budget / 1024, 1),
                     "fits": p.vmem_bytes <= p.budget})
    return rows


#: Every runnable bench section, by the name the ``--sections`` CLI
#: filter (and CI smoke jobs) selects it with. Each entry takes ``full``.
SECTIONS = {
    "table4": lambda full: table4(4_000_000 if full else 1_000_000),
    "table5": lambda full: table5(4_000_000 if full else 1_000_000),
    "kernels": kernel_sweep,
    "streaming": streaming_bench,
    "serve": serve_bench,
    "serve_faults": serve_faults_bench,
    "serve_load": serve_load_sweep,
    "plans": lambda full: plan_rows(),
    "block": block_bench,
}

#: The historical default — what plain ``python benchmarks/throughput.py``
#: has always printed (paper Tables IV/V).
DEFAULT_SECTIONS = "table4,table5"

#: What ``--compiled`` runs when ``--sections`` is not given: the
#: trajectory sections whose compiled-mode numbers ROADMAP item 3 wants,
#: i.e. the same sweep the interpret gate records — directly comparable
#: modulo the platform stamp.
COMPILED_SECTIONS = "kernels,streaming,serve,block"


def main(full: bool = False, sections: str = DEFAULT_SECTIONS):
    rows = []
    for name in sections.split(","):
        rows += SECTIONS[name.strip()](full)
    for r in rows:
        print(",".join(f"{k}={v}" if not isinstance(v, float)
                       else f"{k}={v:.2f}" for k, v in r.items()))
    return rows


def _cli(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="decoder throughput benches (paper Tables IV/V, "
                    "kernel sweep, streaming, serve, block-parallel)")
    ap.add_argument("--full", action="store_true",
                    help="4M-bit workload instead of the 1M-bit quick run")
    ap.add_argument("--sections", default=DEFAULT_SECTIONS,
                    help=f"comma-separated subset of "
                         f"{','.join(SECTIONS)} to run (so a CI smoke "
                         f"job can run one section alone); default: "
                         f"{DEFAULT_SECTIONS}")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="record the bench under the obs tracer and write "
                         "a Chrome trace-event JSON (each section runs as "
                         "one span; plan_decode/kernel_trace events show "
                         "what compiled)")
    ap.add_argument("--compiled", action="store_true",
                    help="compile the Pallas kernels for the real backend "
                         "instead of interpreting them (benchmarks/"
                         "compiled.py sets the platform; BENCH_PLATFORM "
                         "forces a backend; JAX's compilation cache goes "
                         "to JAX_COMPILATION_CACHE_DIR or <checkout>/"
                         ".jax_cache). On a CPU-only "
                         "machine this prints a notice and exits 0 — "
                         "there is nothing honest to record")
    args = ap.parse_args(argv)
    if args.compiled and args.sections == DEFAULT_SECTIONS:
        args.sections = COMPILED_SECTIONS
    names = [s.strip() for s in args.sections.split(",") if s.strip()]
    unknown = [s for s in names if s not in SECTIONS]
    if unknown:
        ap.error(f"unknown section(s) {unknown}; choose from "
                 f"{sorted(SECTIONS)}")
    if not names:
        ap.error("--sections selected nothing")
    if args.compiled:
        from repro.compile_cache import use_compile_cache
        use_compile_cache()        # before anything compiles
        try:                       # script (benchmarks/ on path) or package
            import compiled as _compiled
        except ImportError:
            from benchmarks import compiled as _compiled
        backend = _compiled.set_platform(os.environ.get("BENCH_PLATFORM"))
        if backend == "cpu":
            print("compiled mode: no accelerator backend available — "
                  "skipped (interpret-CPU numbers are the default run; "
                  "a 'compiled' point here would really be the "
                  "interpreter)")
            return []
        set_compiled(True)
        print(f"compiled mode: backend {backend!r}")
    if not args.trace_out:
        return main(full=args.full, sections=",".join(names))

    from repro.obs import Tracer, set_tracer, write_chrome_trace
    tracer = Tracer()
    set_tracer(tracer)
    try:
        rows = []
        for name in names:
            with tracer.span(f"bench:{name}") as sp:
                section = SECTIONS[name](args.full)
                sp.set(rows=len(section))
            rows += section
        for r in rows:
            print(",".join(f"{k}={v}" if not isinstance(v, float)
                           else f"{k}={v:.2f}" for k, v in r.items()))
    finally:
        set_tracer(None)
    obj = write_chrome_trace(tracer, args.trace_out)
    print(f"trace: {len(obj['traceEvents'])} events -> {args.trace_out}")
    return rows


if __name__ == "__main__":
    _cli()
