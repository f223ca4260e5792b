"""Multi-tenant Viterbi decode service demo (repro.serve.DecodeServer).

Opens N sessions across three code configs — the standard K=7 rate-1/2
code, the same code punctured to rate 3/4 (raw punctured pushes, the
server depunctures in-stream), and a K=5 code — streams noisy symbols
chunk by chunk with the slot-based batching server, verifies every
session against its single-stream ``stream_decode`` baseline, and prints
the per-bucket occupancy/latency metrics plus the compiled-plan cache
stats (one trace per bucket shape, regardless of tenant churn).

  PYTHONPATH=src python examples/serve_viterbi.py --sessions 8 --chunks 6

``--chaos`` reruns the same workload under a seeded fault schedule
(repro.testing.faults): injected kernel-launch failures, slow launches
tripping the per-launch deadline, forced plan-cache evictions, and one
tenant pushing NaN-poisoned LLRs until it is quarantined. Healthy
sessions must still verify bit-identical; the demo prints the per-bucket
health and fault counters the server recovered through.

``--trace-out trace.json`` records the whole run with the obs tracer and
writes a Chrome trace-event file — open it in https://ui.perfetto.dev to
see the nested push/launch/retire spans (and, under ``--chaos``, the
retry/degrade recovery sub-spans) on a timeline. ``--metrics-out PREFIX``
writes the final ``metrics_snapshot()`` twice — ``PREFIX.prom``
(Prometheus text exposition, including the stage-latency histogram
series) and ``PREFIX.json`` — so one demo run leaves the complete
observability artifact set (trace + scrape + snapshot).

Durability (PR 8):

  ``--checkpoint-dir DIR`` snapshots the whole server to DIR/serve.ckpt
  after every round (atomic, CRC-validated). ``--kill-at-step N``
  injects a process 'death' at server step N and then demonstrates crash
  recovery live: the client restores a FRESH server from the last
  checkpoint, rewinds its own stream positions to the matching marker,
  and replays — every session still verifies bit-identical at the end.
  ``--resume`` restores server state (cumulative metrics/uptime, any
  carried-over sessions) from DIR/serve.ckpt at startup instead of
  building a fresh server.

Block-parallel decode (PR 9):

  ``--block-frames B`` (or ``auto``) switches the demo to a single
  long-frame (f=2048) tenant config decoded with intra-frame
  block-parallel mode — each frame is split into B overlapped blocks so
  one frame fills a tile the way many short frames do. ``--overlap``
  overrides the per-block warm-up/truncation depth (default ~5
  constraint lengths). The per-window launch latency (from the existing
  stage histograms) is printed either way, so the latency win is visible
  by rerunning with ``--block-frames 1``:

  PYTHONPATH=src python examples/serve_viterbi.py --sessions 2 \\
      --chunks 2 --chunk-frames 2 --block-frames auto

"""
import argparse
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import DecoderConfig, FrameSpec, encode
from repro.core.puncture import puncture
from repro.core.stream import stream_decode
from repro.core.trellis import make_trellis
from repro.channel.sim import awgn, bpsk
from repro.obs import Tracer, set_tracer, write_chrome_trace
from repro.serve import (Backpressure, DecodeServer, PlanCache,
                         SessionQuarantined)


def make_rx(trellis, n, rate, seed, snr=4.0):
    rng = np.random.default_rng(seed)
    bits = jnp.asarray(rng.integers(0, 2, n))
    coded = encode(bits, trellis)
    tx = bpsk(puncture(coded, rate)) if rate != "1/2" \
        else bpsk(coded.reshape(-1))
    rx = np.asarray(awgn(jax.random.PRNGKey(seed), tx, snr))
    return rx if rate != "1/2" else rx.reshape(n, 2)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--chunks", type=int, default=6, help="chunks/session")
    ap.add_argument("--chunk-frames", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chaos", action="store_true",
                    help="run under a seeded fault-injection schedule")
    ap.add_argument("--checkpoint-dir", metavar="DIR",
                    help="snapshot the server to DIR/serve.ckpt after "
                         "every round")
    ap.add_argument("--resume", action="store_true",
                    help="restore the server from the checkpoint dir at "
                         "startup (cumulative metrics carry over)")
    ap.add_argument("--kill-at-step", type=int, default=0, metavar="N",
                    help="inject a crash at server step N, then recover "
                         "from the last checkpoint and replay")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(open in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", metavar="PREFIX",
                    help="write the final metrics_snapshot as PREFIX.prom "
                         "(Prometheus text exposition, incl. the stage "
                         "histograms) and PREFIX.json — with --trace-out "
                         "this leaves the complete observability artifact "
                         "set of a run")
    ap.add_argument("--block-frames", default=None, metavar="B|auto",
                    help="intra-frame block-parallel decode: split each "
                         "frame into B overlapped blocks ('auto' lets the "
                         "planner pick); any value switches the demo to a "
                         "long-frame (f=2048) workload, so '1' is the "
                         "sequential baseline of the same workload")
    ap.add_argument("--overlap", type=int, default=None, metavar="OV",
                    help="per-block warm-up/truncation overlap in trellis "
                         "stages (default: policy, ~5 constraint lengths)")
    args = ap.parse_args(argv)
    blk = args.block_frames
    if blk is not None and blk != "auto":
        blk = int(blk)
    if args.kill_at_step and not args.checkpoint_dir:
        args.checkpoint_dir = tempfile.mkdtemp(prefix="serve_ckpt_")

    tracer = None
    if args.trace_out:
        tracer = Tracer()
        set_tracer(tracer)          # lights up serve + stream + planner

    k5 = make_trellis(5, (0o23, 0o35))
    spec12 = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
    spec34 = FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21)
    cfgs = [("K7 r1/2", DecoderConfig(spec=spec12)),
            ("K7 r3/4", DecoderConfig(spec=spec34, rate="3/4")),
            ("K5 r1/2", DecoderConfig(trellis=k5, spec=spec12))]
    if blk is not None:
        # short frames never block (policy threshold) — the latency win
        # is the point, so block mode runs one long-frame tenant config;
        # --block-frames 1 is the sequential baseline of that workload
        spec_long = FrameSpec(f=2048, v1=32, v2=32, f0=32, v2s=32)
        cfgs = [("K7 long", DecoderConfig(spec=spec_long, block_frames=blk,
                                          overlap=args.overlap))]

    from repro.testing import FaultInjector, FaultSpec
    from repro.testing.faults import InjectedCrash
    specs = []
    if args.chaos:
        # the LAST session is the poisoned tenant (sids count from 0)
        specs += [FaultSpec("launch_error", every=5),
                  FaultSpec("launch_slow", every=7, delay_s=0.05),
                  FaultSpec("plan_cache_miss", every=6),
                  FaultSpec("corrupt_llr", every=2, mode="nan",
                            sessions=(args.sessions - 1,))]
    if args.kill_at_step:
        specs.append(FaultSpec("crash_at_step", after=args.kill_at_step,
                               count=1))
    faults = FaultInjector(*specs, seed=3) if specs else None
    cache = PlanCache()
    ck_path = None
    if args.checkpoint_dir:
        os.makedirs(args.checkpoint_dir, exist_ok=True)
        ck_path = os.path.join(args.checkpoint_dir, "serve.ckpt")
    if args.resume and ck_path and os.path.exists(ck_path):
        srv = DecodeServer.restore(ck_path, cache=cache, faults=faults)
        for sid in list(srv._sessions):
            tail = srv.close_session(sid)
            print(f"resumed: closed carried-over session {sid} "
                  f"({len(tail)} undelivered bits recovered)")
        print(f"resumed from {ck_path}: cumulative uptime "
              f"{srv.metrics_snapshot()['totals']['uptime_s']:.2f}s, "
              f"restore #{srv.checkpoint_restores}")
    else:
        srv = DecodeServer(slots=args.slots, max_sessions=args.sessions,
                           queue_depth=4, cache=cache, faults=faults,
                           launch_timeout_s=0.03 if args.chaos else None,
                           max_retries=1, backoff_s=0.0,
                           quarantine_after=2)
    tenants = []
    for i in range(args.sessions):
        name, cfg = cfgs[i % len(cfgs)]
        n = args.chunks * args.chunk_frames * cfg.spec.f
        rx = make_rx(cfg.trellis, n, cfg.rate, seed=i)
        sid = srv.open_session(cfg, chunk_frames=args.chunk_frames)
        per = rx.shape[0] // args.chunks
        tenants.append(dict(sid=sid, name=name, cfg=cfg, rx=rx, n=n,
                            chunks=[rx[j * per:(j + 1) * per]
                                    for j in range(args.chunks)], out=[],
                            quarantined=None))
    print(f"{args.sessions} sessions / {len(srv.buckets())} buckets, "
          f"chunk={args.chunk_frames} frames, slots={args.slots}"
          + (", CHAOS schedule on" if args.chaos else ""))

    # client-side recovery marker: (next round, bits delivered per tenant,
    # quarantine states) as of the last checkpoint — on a crash the client
    # rewinds to it and replays against the restored server
    mark = None
    if ck_path:
        srv.checkpoint(ck_path)
        mark = (0, [0] * len(tenants), [None] * len(tenants))
    r = 0
    while r < args.chunks:
        try:
            for t in tenants:
                if t["quarantined"] is not None:
                    continue
                try:
                    srv.push(t["sid"], t["chunks"][r])
                except Backpressure as e:
                    # the structured hint says how many steps clear it
                    for _ in range(e.retry_after_steps or 1):
                        srv.step()
                    srv.push(t["sid"], t["chunks"][r])
                except SessionQuarantined as e:
                    t["quarantined"] = e
            while srv.step():
                pass
            for t in tenants:
                if t["quarantined"] is None:
                    try:
                        t["out"].append(srv.poll(t["sid"]))
                    except SessionQuarantined as e:
                        t["quarantined"] = e
            r += 1
            if ck_path:
                srv.checkpoint(ck_path)
                mark = (r, [sum(len(o) for o in t["out"]) for t in tenants],
                        [t["quarantined"] for t in tenants])
        except InjectedCrash as e:
            print(f"\nCRASH: {e} — restoring a fresh server from {ck_path}")
            srv = DecodeServer.restore(ck_path, cache=cache, faults=faults)
            r, delivered, quar = mark
            for t, nb, q in zip(tenants, delivered, quar):
                acc = (np.concatenate(t["out"]) if t["out"]
                       else np.zeros(0, np.int32))
                t["out"] = [acc[:nb]]
                t["quarantined"] = q
            print(f"restored (restore #{srv.checkpoint_restores}); "
                  f"replaying from round {r}")
    for t in tenants:
        t["out"].append(srv.close_session(t["sid"]))  # quarantined too

    total = 0
    poisoned_sids = set(faults._specs["corrupt_llr"][0].sessions) \
        if args.chaos else set()
    for t in tenants:
        if t["sid"] in poisoned_sids:
            continue                      # its input WAS corrupted
        got = np.concatenate(t["out"])[:t["n"]]
        want = stream_decode(t["cfg"], t["rx"], t["n"],
                             chunk_frames=args.chunk_frames)
        assert np.array_equal(got, want), f"{t['name']} sid={t['sid']}"
        total += t["n"]

    snap = srv.metrics_snapshot()
    tot = snap["totals"]
    # throughput/uptime come from the metrics themselves now — no more
    # hand-timed loop around the workload
    print(f"decoded {total} verified bits in {tot['uptime_s']*1e3:.0f} ms "
          f"({tot['mbps']:.2f} Mb/s aggregate) — every healthy session "
          f"bit-identical to its solo stream_decode")
    for t in tenants:
        if t["quarantined"] is not None:
            e = t["quarantined"]
            print(f"quarantined: {t['name']} sid={e.sid} after "
                  f"{e.strikes} poisoned pushes ({e.reason})")

    print(f"{'bucket':<28}{'launches':>9}{'windows':>9}{'occup':>7}"
          f"{'p50 ms':>8}{'p99 ms':>8}{'Mb/s':>7}  {'health':<9}")
    for row in snap["buckets"]:
        print(f"{row['bucket']:<28}{row['launches']:>9}{row['windows']:>9}"
              f"{row['occupancy']:>7.2f}{row['p50_ms']:>8.1f}"
              f"{row['p99_ms']:>8.1f}{row['mbps']:>7.2f}  "
              f"{row['health']:<9}")
    print(f"{'stage':<16}{'count':>7}{'p50 ms':>8}{'p99 ms':>8}"
          f"{'max ms':>8}")
    for stage, s in sorted(snap["stages"].items()):
        print(f"{stage:<16}{s['count']:>7}{s['p50']:>8.2f}{s['p99']:>8.2f}"
              f"{s['max']:>8.2f}")
    la = snap["stages"].get("launch_ms")
    if la and la.get("count"):
        blocked = blk not in (None, 1)
        mode = (f"block-parallel ({args.block_frames} blocks/frame)"
                if blocked else "sequential scan")
        hint = (" — rerun with --block-frames 1 to compare" if blocked
                else " — rerun with --block-frames auto for the blocked "
                     "plan")
        print(f"per-window launch latency [{mode}]: p50 {la['p50']:.2f} ms, "
              f"p99 {la['p99']:.2f} ms over {la['count']} launches{hint}")
    print("plan cache:", snap["plan_cache"])
    if ck_path:
        print(f"checkpoints: {snap['checkpoint']['saves']} saved, "
              f"{snap['checkpoint']['restores']} restores -> {ck_path}")
    if args.chaos:
        print(f"faults recovered: {tot['launch_errors']} launch errors, "
              f"{tot['timeouts']} timeouts, {tot['retries']} retries, "
              f"{tot['degraded']} degraded launches, "
              f"{tot['cache_refreshes']} cache refreshes, "
              f"{tot['sanitized_values']} LLRs sanitized, "
              f"{tot['quarantined']} quarantined — overall "
              f"health={tot['health']}")
        print("injector:", snap["faults"])
    if args.metrics_out:
        from repro.obs import prometheus_text, write_metrics_json
        prom_path = args.metrics_out + ".prom"
        json_path = args.metrics_out + ".json"
        with open(prom_path, "w") as fh:
            fh.write(prometheus_text(snap))
        write_metrics_json(snap, json_path)
        print(f"metrics: exposition -> {prom_path}, snapshot -> "
              f"{json_path}")
    if tracer is not None:
        obj = write_chrome_trace(tracer, args.trace_out)
        set_tracer(None)
        print(f"trace: {len(obj['traceEvents'])} events -> "
              f"{args.trace_out} (open in https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
