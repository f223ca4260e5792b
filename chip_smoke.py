#!/usr/bin/env python3
"""Smoke run of the decode service on a TPU, through its user entry points.

    python chip_smoke.py [--seed N]       # phases A-C on one chip
    python chip_smoke.py --chips 4        # phase D on four chips

One process; no child touches JAX. The data is made on the device from
``--seed``: random bits, convolutionally encoded, BPSK over AWGN at
3 dB Eb/N0 (``repro.channel.sim``'s recipe).

  A  single shot: ``make_decoder(DecoderConfig(spec=...))`` on 2^21 bits
     of the K=7 (171,133) code; bit-identical to the reference backend
     on the same LLRs (run on the host CPU), BER <= 1e-3 against the sent
     bits. The reference backend run on the chip, through ``make_decoder``
     and through the serve layer's failover batch program, must give the
     same bits.
  B  serve: one ``DecodeServer(slots=16)``, 256 sessions in the
     ``benchmarks/throughput.py`` serve mix (half K=7 rate 1/2, a quarter
     K=7 rate 3/4 pushing raw punctured symbols, a quarter K=5 rate 1/2),
     16-frame pushes for 4 rounds; every session bit-identical to its
     reference decode, one compile per bucket shape.
  C  long frame: a ``low_latency=True`` session (block-parallel decode)
     and a sequential one on the same f=4096 stream; BERs within 1e-3.
  D  ``--chips 4`` only: phase B's K=7 rate-1/2 sessions on
     ``DecodeServer(mesh=frame_mesh())``, bit-identical to the same
     sessions on one chip, every decoded batch sharded over 4 devices.

Every phase also checks that the compiled kernel did the work: each
``kernel_trace`` event says ``interpret=False`` and every serve bucket
shows zero launch errors, timeouts, retries, degraded launches and
breaker trips (the server would otherwise hide a failing kernel behind
its reference fallback). Any failed check raises.

Output: the JAX/libtpu versions, the launched kernel plans, and per-phase
wall times — compile apart from steady state, smoke figures and not a
benchmark — then, as the last line, ``{"ok": true, "device": {...}}``.
Without a TPU it prints no result and exits nonzero.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np                                       # noqa: E402
import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402

from repro.channel.sim import awgn, bpsk                 # noqa: E402
from repro.compile_cache import use_compile_cache       # noqa: E402
from repro.core import (DecoderConfig, FrameSpec, STD_K7,  # noqa: E402
                        encode, make_decoder, make_trellis, puncture)
from repro.core.framed import frame_llr                  # noqa: E402
from repro.obs import Tracer, set_tracer                 # noqa: E402
from repro.serve import DecodeServer, PlanCache          # noqa: E402

EBN0_DB = 3.0
SPEC_A = FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45)
SPEC_12 = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
SPEC_34 = FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21)
SPEC_C = FrameSpec(f=4096, v1=32, v2=32, f0=32, v2s=32)
K5 = make_trellis(5, (0o23, 0o35))
#: bucket counters that must stay 0 for the fast path to have done the work
FAULTS = ("launch_errors", "timeouts", "retries", "degraded",
          "breaker_trips")


class SmokeFailure(RuntimeError):
    """A smoke check failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(*parts) -> None:
    print(*parts, flush=True)


def kernel_cfg(over: dict, **kw) -> DecoderConfig:
    """A DecoderConfig on the platform defaults, plus ``over`` (the CPU
    rehearsal pins the kernel backend there); it must name the unified
    kernel."""
    cfg = DecoderConfig(**kw, **over)
    check(cfg.backend == "kernel",
          f"config does not select the kernel: {cfg}")
    return cfg


def reference(cfg: DecoderConfig, stream, n: int) -> np.ndarray:
    """The reference backend's decode of ``stream`` (or of a batch of
    streams, leading axis), run on the host CPU: independent of the chip's
    compiler, which has miscompiled the reference program before (see
    PERF.md)."""
    host = jax.devices("cpu")[0]
    dec = make_decoder(dataclasses.replace(cfg, backend="reference"))
    with jax.default_device(host):
        stream = jax.device_put(np.asarray(stream), host)
        if stream.ndim > (1 if cfg.punctured else 2):
            return np.asarray(jax.vmap(lambda s: dec(s, n))(stream))
        return np.asarray(dec(stream, n))


def channel(key, sessions: int, n: int, trellis, rate: str = "1/2"):
    """(sessions, n) sent bits and what each session receives: (n, beta)
    soft symbols at rate 1/2, else the raw punctured symbol stream."""
    kb, kn = jax.random.split(key)
    bits = jax.random.bernoulli(kb, 0.5, (sessions, n)).astype(jnp.int32)
    coded = jax.vmap(lambda b: encode(b, trellis))(bits)
    if rate != "1/2":
        coded = jax.vmap(lambda c: puncture(c, rate))(coded)
    return bits, awgn(kn, bpsk(coded), EBN0_DB)


class Traced:
    """Installs a fresh process-global tracer for one phase and checks the
    kernel compiles it saw: at least one, each with ``interpret`` as the
    config asked for."""

    def __init__(self, interpret: bool):
        self.interpret = interpret
        self.tracer = Tracer()

    def __enter__(self):
        set_tracer(self.tracer)
        return self

    def __exit__(self, *exc):
        set_tracer(None)

    def kernel_plans(self) -> list:
        evs = [r.attrs for r in self.tracer.spans()
               if r.name == "kernel_trace"]
        check(evs, "no kernel_trace event: the kernel did not compile in "
                   "this phase")
        bad = [e for e in evs if e["interpret"] is not self.interpret]
        check(not bad, f"kernel traced with interpret != "
                       f"{self.interpret}: {bad}")
        return evs

    def launch_shapes(self) -> set:
        return {(r.attrs["bucket"], r.attrs["frames"])
                for r in self.tracer.spans() if r.name == "launch"}


def check_buckets(srv: DecodeServer) -> list:
    rows = srv.metrics_snapshot()["buckets"]
    for row in rows:
        bad = {c: row[c] for c in FAULTS if row[c]}
        check(not bad and row["health"] == "ok",
              f"bucket {row['bucket']} fell off the fast path: {bad}, "
              f"health={row['health']}, last_error={row.get('last_error')}")
    return rows


def log_plans(phase: str, plans) -> None:
    for p in plans:
        log(f"  {phase} kernel plan: " + json.dumps(p, sort_keys=True))


# -- phase A ---------------------------------------------------------------
def phase_a(key, *, frames: int = 8192, over: dict | None = None,
            max_ber: float = 1e-3) -> dict:
    """Single-shot decode of ``frames`` f=256 frames of the K=7 code."""
    over = over or {}
    n = frames * SPEC_A.f
    bits, rx = channel(key, 1, n, STD_K7)
    bits, llr = bits[0], rx[0]
    cfg = kernel_cfg(over, spec=SPEC_A)
    with Traced(cfg.interpret) as tr:
        dec = make_decoder(cfg)
        t0 = time.perf_counter()
        out = dec(llr, n).block_until_ready()
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = dec(llr, n).block_until_ready()
        t_steady = time.perf_counter() - t0
        plans = tr.kernel_plans()
    want = reference(cfg, llr, n)
    out, bits = np.asarray(out), np.asarray(bits)
    ber = float(np.mean(out != bits))
    check(np.array_equal(out, want),
          f"phase A: kernel != reference on {int((out != want).sum())} "
          f"bits (BER kernel {ber}, reference {np.mean(want != bits)})")
    check(ber <= max_ber, f"phase A: BER {ber} > {max_ber}")
    # the reference backend on this device too: the serve layer degrades
    # and fails over to it, so it must be exact here, not only on the host
    ref_cfg = dataclasses.replace(cfg, backend="reference")
    frames_in = jax.jit(lambda x: frame_llr(x, SPEC_A))(llr)
    on_device = {
        "make_decoder": make_decoder(ref_cfg)(llr, n),
        "serve failover batch": PlanCache().batch_decoder(
            ref_cfg, frames)(frames_in).reshape(-1)[:n]}
    for name, got in on_device.items():
        got = np.asarray(got)
        check(np.array_equal(got, want),
              f"phase A: reference backend ({name}) on "
              f"{jax.devices()[0].platform} != the host-CPU reference on "
              f"{int((got != want).sum())} bits")
    log_plans("A", plans)
    log(f"phase A: {n} bits, BER {ber:.3e}, bit-identical to the host-CPU "
        f"reference, as are the reference backend's make_decoder and serve "
        f"failover programs on this device; "
        f"smoke figure, not a benchmark: first call (compile + run) "
        f"{t_first:.3f} s, steady call {t_steady:.4f} s")
    return {"bits": n, "ber": ber, "first_s": t_first, "steady_s": t_steady}


# -- phase B / D -----------------------------------------------------------
def serve_mix(sessions: int, over: dict) -> list:
    """The serve-bench code mix: (cfg, rate) for each session."""
    k7_12 = kernel_cfg(over, spec=SPEC_12)
    k7_34 = kernel_cfg(over, spec=SPEC_34, rate="3/4")
    k5_12 = kernel_cfg(over, trellis=K5, spec=SPEC_12)
    q = sessions // 4
    return [k7_12] * (sessions - 2 * q) + [k7_34] * q + [k5_12] * q


def serve_streams(key, cfgs: list, rounds: int, chunk: int):
    """Per session: (cfg, raw pushes, n bits, reference bits). Sessions of
    one config share one batched channel draw and one batched reference
    decode."""
    out = [None] * len(cfgs)
    groups: dict = {}
    for i, cfg in enumerate(cfgs):
        groups.setdefault(cfg, []).append(i)
    for g, (cfg, idx) in enumerate(groups.items()):
        n = rounds * chunk * cfg.spec.f
        _, rx = channel(jax.random.fold_in(key, g), len(idx), n,
                        cfg.trellis, cfg.rate)
        rx = np.asarray(rx)
        want = reference(cfg, rx, n)
        per = rx.shape[1] // rounds
        for j, i in enumerate(idx):
            out[i] = (cfg, [rx[j, r * per:(r + 1) * per]
                            for r in range(rounds)], n, want[j])
    return out


def serve(streams: list, *, slots: int, chunk: int, cache: PlanCache,
          mesh=None, on_step=None):
    """Run the sessions through one DecodeServer: every session pushes one
    chunk per round; returns (server, per-session bits)."""
    srv = DecodeServer(slots=slots, max_sessions=len(streams), mesh=mesh,
                       cache=cache)
    sids = [srv.open_session(cfg, chunk_frames=chunk)
            for cfg, _, _, _ in streams]
    got = {sid: [] for sid in sids}

    def step_all():
        while srv.step():
            if on_step is not None:
                on_step(srv)

    for r in range(len(streams[0][1])):
        for sid, (_, pushes, _, _) in zip(sids, streams):
            srv.push(sid, pushes[r])
        step_all()
        for sid in sids:
            got[sid].append(srv.poll(sid))
    for sid in sids:
        got[sid].append(srv.close_session(sid))
    return srv, [np.concatenate(got[sid])[:n]
                 for sid, (_, _, n, _) in zip(sids, streams)]


def run_serve(name: str, streams: list, *, slots: int, chunk: int,
              interpret: bool, mesh=None, on_step=None):
    """Serve the streams twice on one plan cache (cold, then warm): checks
    bits against the references, zero faults, the kernel traces, and one
    compile per launched bucket shape. Returns the warm run's bits."""
    cache = PlanCache()
    times = []
    with Traced(interpret) as tr:
        for _ in range(2):
            t0 = time.perf_counter()
            srv, bits = serve(streams, slots=slots, chunk=chunk, cache=cache,
                              mesh=mesh, on_step=on_step)
            times.append(time.perf_counter() - t0)
            rows = check_buckets(srv)
            for i, (b, (_, _, _, want)) in enumerate(zip(bits, streams)):
                check(np.array_equal(b, want),
                      f"phase {name}: session {i} != its reference decode "
                      f"on {int((b != want).sum())} bits")
        plans = tr.kernel_plans()
        shapes = tr.launch_shapes()
    traces = cache.stats()["traces"]
    check(traces == len(shapes),
          f"phase {name}: {traces} compiles for {len(shapes)} launched "
          f"bucket shapes {sorted(shapes)}")
    log_plans(name, plans)
    for b in srv.buckets():
        log(f"  {name} bucket {b.id}: plan "
            + json.dumps(b.plan.kernel_kwargs(), sort_keys=True))
    nbits = sum(len(b) for b in bits)
    log(f"phase {name}: {len(streams)} sessions, {nbits} bits in "
        f"{len(rows)} buckets, {traces} compiles for {len(shapes)} bucket "
        f"shapes, all bit-identical to reference, zero faults; smoke "
        f"figure, not a benchmark: cold pass {times[0]:.3f} s, warm pass "
        f"{times[1]:.3f} s")
    return bits


def phase_b(key, *, sessions: int = 256, rounds: int = 4, chunk: int = 16,
            slots: int = 16, over: dict | None = None) -> list:
    over = over or {}
    cfgs = serve_mix(sessions, over)
    streams = serve_streams(key, cfgs, rounds, chunk)
    return run_serve("B", streams, slots=slots, chunk=chunk,
                     interpret=cfgs[0].interpret)


def phase_d(key, mesh, *, sessions: int = 128, rounds: int = 4,
            chunk: int = 16, slots: int = 16,
            over: dict | None = None) -> None:
    """Phase B's K=7 rate-1/2 sessions sharded over ``mesh`` vs one
    device."""
    over = over or {}
    cfg = kernel_cfg(over, spec=SPEC_12)
    streams = serve_streams(key, [cfg] * sessions, rounds, chunk)
    ndev = int(mesh.devices.size)
    spread = []

    def on_step(srv):
        for b in srv.buckets():
            spread.extend(len(out.sharding.device_set)
                          for out, *_ in b.inflight)

    one = run_serve("D/one-chip", streams, slots=slots, chunk=chunk,
                    interpret=cfg.interpret)
    many = run_serve("D/mesh", streams, slots=slots, chunk=chunk,
                     interpret=cfg.interpret, mesh=mesh, on_step=on_step)
    for i, (a, b) in enumerate(zip(one, many)):
        check(np.array_equal(a, b), f"phase D: session {i} differs "
                                    f"between one device and the mesh")
    check(spread and all(s == ndev for s in spread),
          f"phase D: decoded batches not sharded over all {ndev} devices "
          f"(devices per batch: {sorted(set(spread))})")
    log(f"phase D: {sessions} sessions bit-identical on one device and on "
        f"a {ndev}-device mesh; {len(spread)} decoded batches each sharded "
        f"over {ndev} devices")


# -- phase C ---------------------------------------------------------------
def phase_c(key, *, frames: int = 16, chunk: int = 8,
            over: dict | None = None) -> dict:
    """A low-latency (block-parallel) and a sequential session on the
    same long-frame stream."""
    over = over or {}
    cfg = kernel_cfg(over, spec=SPEC_C)
    n = frames * SPEC_C.f
    bits, rx = channel(key, 1, n, STD_K7)
    bits, rx = np.asarray(bits[0]), np.asarray(rx[0])
    per = chunk * SPEC_C.f
    cache = PlanCache()
    times = []
    with Traced(cfg.interpret) as tr:
        for _ in range(2):
            t0 = time.perf_counter()
            srv = DecodeServer(slots=2, cache=cache)
            sids = [srv.open_session(cfg, chunk, low_latency=True),
                    srv.open_session(cfg, chunk)]
            got = {sid: [] for sid in sids}
            for i in range(0, n, per):
                for sid in sids:
                    srv.push(sid, rx[i:i + per])
                while srv.step():
                    pass
                for sid in sids:
                    got[sid].append(srv.poll(sid))
            for sid in sids:
                got[sid].append(srv.close_session(sid))
            times.append(time.perf_counter() - t0)
            check_buckets(srv)
        plans = tr.kernel_plans()
    blocked, seq = (np.concatenate(got[sid])[:n] for sid in sids)
    check(sorted({p["block_frames"] > 1 for p in plans}) == [False, True],
          f"phase C: expected one blocked and one sequential plan: {plans}")
    ber_b = float(np.mean(blocked != bits))
    ber_s = float(np.mean(seq != bits))
    check(abs(ber_b - ber_s) <= 1e-3,
          f"phase C: blocked BER {ber_b} vs sequential {ber_s}")
    log_plans("C", plans)
    log(f"phase C: f={SPEC_C.f}, {n} bits, BER blocked {ber_b:.3e} vs "
        f"sequential {ber_s:.3e}, zero faults; smoke figure, not a "
        f"benchmark: cold pass {times[0]:.3f} s, warm pass "
        f"{times[1]:.3f} s")
    return {"ber_blocked": ber_b, "ber_sequential": ber_s}


def _version(pkg: str) -> str:
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs phase D (frame-sharded serve) and its "
                         "one-chip comparison, nothing else")
    args = ap.parse_args(argv)
    cache_dir = use_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"jax {jax.__version__}, jaxlib {_version('jaxlib')}, libtpu "
        f"{_version('libtpu')}; device {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache_dir} ({warm} entries at start)")
    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        from repro.distributed.stream import frame_mesh
        phase_d(jax.random.fold_in(key, 3), frame_mesh(devs[:4]))
    else:
        phase_a(jax.random.fold_in(key, 0))
        phase_b(jax.random.fold_in(key, 1))
        phase_c(jax.random.fold_in(key, 2))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
