"""Durable sessions (PR 8): StreamContext state round-trips, serve
checkpoint/restore bit-identity, crash recovery, circuit breakers +
device failover, and the corrupt-checkpoint rejection contract."""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import DecoderConfig, FrameSpec, STD_K7, encode
from repro.core.puncture import puncture
from repro.core.stream import STATE_VERSIONS, StreamContext, stream_decode
from repro.channel.sim import awgn, bpsk
from repro.serve import (Breaker, CheckpointError, DecodeServer, Draining,
                         PlanCache, save_checkpoint)
from repro.testing.faults import (FaultInjector, FaultSpec, InjectedCrash)

from _hypothesis_compat import given, settings, st

SPEC = FrameSpec(f=64, v1=16, v2=20)
SPEC34 = FrameSpec(f=63, v1=21, v2=21)


def _rx(n, rate="1/2", seed=0, snr=4.0, trellis=STD_K7):
    """Noisy received stream: (n, 2) soft symbols, or the raw punctured
    flat stream for punctured rates."""
    rng = np.random.default_rng(seed)
    bits = jnp.asarray(rng.integers(0, 2, n))
    coded = encode(bits, trellis)
    tx = bpsk(puncture(coded, rate)) if rate != "1/2" \
        else bpsk(coded.reshape(-1))
    rx = np.asarray(awgn(jax.random.PRNGKey(seed), tx, snr))
    return rx if rate != "1/2" else rx.reshape(n, 2)


def _windows(ctx, pieces, flush):
    """Feed ``pieces`` then (optionally) flush; returns the emitted
    windows as comparable (frames-bytes, n_bits) pairs."""
    out = []
    for p in pieces:
        ctx.append(p)
        out += ctx.take_windows()
    if flush:
        out += ctx.flush_chunks()
    spec = ctx.spec
    return [(w.frames(spec).tobytes(), w.n_bits) for w in out]


# -- StreamContext state round-trip ---------------------------------------
@settings(max_examples=16, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from(["1/2", "3/4"]),
       st.sampled_from(list(STATE_VERSIONS)))
def test_context_state_roundtrip_bit_identical(seed, rate, version):
    """The property the whole durability story rests on: snapshot a
    context mid-stream at a random point of a random push schedule,
    restore it into a FRESH context, feed both the same remaining input —
    every subsequent window (and the flush tail) is bit-identical."""
    rng = np.random.default_rng(seed)
    spec = SPEC if rate == "1/2" else SPEC34
    n = int(rng.integers(2, 14)) * spec.f
    rx = _rx(n, rate, seed=seed % 1000)
    flat = rx.reshape(-1)
    # random ragged cut points (raw symbol granularity — mid-stage cuts
    # for the punctured rate exercise the raw remainder + phase carry)
    k = int(rng.integers(2, 7))
    cuts = np.sort(rng.choice(np.arange(1, flat.shape[0]), k, replace=False))
    pieces = np.split(flat, cuts)
    if rate == "1/2":
        # rate-1/2 pushes are (s, 2) stages; round the cuts to pairs
        pieces = np.split(rx, np.unique(np.clip(cuts // 2, 1, n - 1)))
    cut = int(rng.integers(1, len(pieces)))
    C = int(rng.integers(1, 4))

    ctx = StreamContext(spec, STD_K7.beta, C, rate)
    for p in pieces[:cut]:
        ctx.append(p)
        ctx.take_windows()
    state = ctx.state_dict(version=version)
    state = json.loads(json.dumps(state))       # a real serialization trip

    fresh = StreamContext(spec, STD_K7.beta, C, rate)
    fresh.load_state(state)
    assert fresh.n_in == ctx.n_in and fresh.n_out == ctx.n_out
    got = _windows(fresh, pieces[cut:], flush=True)
    want = _windows(ctx, pieces[cut:], flush=True)
    assert got == want


def test_context_state_rejects_bad_version_geometry_and_crc():
    ctx = StreamContext(SPEC, STD_K7.beta, 2, "1/2")
    ctx.append(_rx(3 * 64, seed=1))
    ctx.take_windows()
    state = ctx.state_dict()
    with pytest.raises(ValueError, match="version"):
        ctx.state_dict(version=99)
    bad = dict(state, version=99)
    with pytest.raises(ValueError, match="version"):
        StreamContext(SPEC, STD_K7.beta, 2, "1/2").load_state(bad)
    # geometry mismatch: different chunk_frames would decode differently
    with pytest.raises(ValueError, match="geometry"):
        StreamContext(SPEC, STD_K7.beta, 3, "1/2").load_state(state)
    with pytest.raises(ValueError, match="geometry"):
        StreamContext(SPEC34, STD_K7.beta, 2, "3/4").load_state(state)
    # v2 carry corruption trips the CRC, and nothing half-loads
    target = StreamContext(SPEC, STD_K7.beta, 2, "1/2")
    corrupt = dict(state, buf="AAAA" + state["buf"][4:])
    with pytest.raises(ValueError, match="CRC"):
        target.load_state(corrupt)
    assert target.n_in == 0                     # untouched by the failure
    with pytest.raises(ValueError, match="state dict"):
        target.load_state({"nonsense": True})


# -- server checkpoint / restore ------------------------------------------
def test_server_checkpoint_restore_bit_identical_with_queued_windows():
    """Kill a server with work at EVERY pipeline position — undelivered
    ready bits, still-queued windows, half-pushed carry — restore in a
    'fresh process', finish both; the restored server's bits match the
    uninterrupted run and the solo stream_decode baseline."""
    cfg12 = DecoderConfig(spec=SPEC)
    cfg34 = DecoderConfig(spec=SPEC34, rate="3/4")
    n = 10 * 64
    rxs = {0: _rx(n, seed=20), 1: _rx(n, seed=21)}
    rx34 = _rx(630, "3/4", seed=22)

    def build():
        srv = DecodeServer(slots=2, cache=PlanCache())
        a = srv.open_session(cfg12, chunk_frames=2)
        b = srv.open_session(cfg12, chunk_frames=2)
        c = srv.open_session(cfg34, chunk_frames=3)
        return srv, (a, b, c)

    srv, (a, b, c) = build()
    srv.push(a, rxs[0][: 6 * 64])
    srv.push(b, rxs[1][: 4 * 64 + 13])          # ragged: carry mid-frame
    srv.push(c, rx34[:301])                     # mid-stage raw remainder
    srv.step()                                   # some launched (depth=1)
    srv.push(a, rxs[0][6 * 64:8 * 64])          # some still queued
    path = "/tmp/test_serve_ckpt.json"
    srv.checkpoint(path)
    assert any(b_.queue for b_ in srv.buckets())  # the cut really had
    # queued windows (the checkpoint must carry them)

    srv2 = DecodeServer.restore(path, cache=PlanCache())
    assert srv2.num_sessions == 3
    finish = [(a, rxs[0][8 * 64:], n, cfg12, np.concatenate([rxs[0]])),
              (b, rxs[1][4 * 64 + 13:], n, cfg12, rxs[1]),
              (c, rx34[301:], 630, cfg34, rx34)]
    outs = {}
    for which, s in (("live", srv), ("restored", srv2)):
        got = {}
        for sid, rest, n_bits, _cfg, _full in finish:
            s.push(sid, rest)
        s.drain()
        for sid, rest, n_bits, _cfg, _full in finish:
            got[sid] = np.concatenate(
                [s.poll(sid), s.close_session(sid)])[:n_bits]
        outs[which] = got
    for sid, _rest, n_bits, cfg, full in finish:
        cf = 2 if cfg is cfg12 else 3
        want = stream_decode(cfg, full, n_bits, chunk_frames=cf)
        assert np.array_equal(outs["live"][sid], want)
        assert np.array_equal(outs["restored"][sid], want)


def test_restore_preserves_metrics_counters_and_uptime():
    cfg = DecoderConfig(spec=SPEC)
    faults = FaultInjector(FaultSpec("launch_error", every=2), seed=0)
    srv = DecodeServer(slots=2, cache=PlanCache(), faults=faults,
                       max_retries=1, backoff_s=0.0)
    sid = srv.open_session(cfg, chunk_frames=2)
    srv.push(sid, _rx(8 * 64, seed=30))
    srv.drain()
    before = srv.metrics_snapshot()
    assert before["totals"]["launch_errors"] > 0
    path = "/tmp/test_serve_ckpt_metrics.json"
    srv.checkpoint(path)
    srv2 = DecodeServer.restore(path, cache=PlanCache())
    after = srv2.metrics_snapshot()
    for c in ("launch_errors", "retries", "degraded", "launches", "bits"):
        assert after["totals"][c] == before["totals"][c], c
    # uptime continues (cumulative story), it does not restart at ~0
    assert after["totals"]["uptime_s"] >= before["totals"]["uptime_s"]
    assert after["checkpoint"] == {"saves": 1, "restores": 1}
    # stage histograms survive too
    assert (after["stages"]["launch_ms"]["count"]
            == before["stages"]["launch_ms"]["count"])


def test_checkpoint_after_tenant_churn_restores():
    """Regression: buckets (and their breakers) outlive their last
    session in the saving server, so a checkpoint taken after normal
    tenant churn (open -> drain -> close) carries breaker state for a
    bucket restore cannot rebuild. The orphan breaker entry must be
    dropped, not rejected as 'unknown bucket' — and a bucket that DOES
    still have a live session keeps its breaker across the trip."""
    cfg12 = DecoderConfig(spec=SPEC)
    cfg34 = DecoderConfig(spec=SPEC34, rate="3/4")
    srv = DecodeServer(slots=2, cache=PlanCache())
    churned = srv.open_session(cfg12, chunk_frames=2)
    srv.push(churned, _rx(4 * 64, seed=80))
    srv.drain()
    srv.close_session(churned)                   # bucket stays in _buckets
    live = srv.open_session(cfg34, chunk_frames=3)
    rx34 = _rx(630, "3/4", seed=81)
    srv.push(live, rx34[:301])
    path = "/tmp/test_serve_ckpt_churn.json"
    srv.checkpoint(path)

    srv2 = DecodeServer.restore(path, cache=PlanCache())
    assert srv2.num_sessions == 1
    # only the live session's bucket came back; its breaker survived
    assert list(srv2.metrics_snapshot()["breakers"].values()) \
        == [{"state": "closed", "trips": 0, "consecutive": 0}]
    # the surviving stream resumes bit-identically...
    srv2.push(live, rx34[301:])
    got = np.concatenate([srv2.poll(live), srv2.close_session(live)])[:630]
    assert np.array_equal(got, stream_decode(cfg34, rx34, 630,
                                             chunk_frames=3))
    # ...and fresh tenants of the churned config admit + decode normally
    rx = _rx(4 * 64, seed=82)
    sid = srv2.open_session(cfg12, chunk_frames=2)
    srv2.push(sid, rx)
    got = np.concatenate([srv2.poll(sid), srv2.close_session(sid)])
    assert np.array_equal(got, stream_decode(cfg12, rx, 4 * 64,
                                             chunk_frames=2))


def test_checkpoint_all_sessions_closed_restores_empty():
    """The reviewer's minimal repro: every session closed, then
    checkpoint — the restore must succeed with zero sessions, not raise
    CheckpointError over the left-behind bucket's breaker state."""
    cfg = DecoderConfig(spec=SPEC)
    srv = DecodeServer(slots=2, cache=PlanCache())
    sid = srv.open_session(cfg, chunk_frames=2)
    srv.push(sid, _rx(4 * 64, seed=83))
    srv.drain()
    srv.close_session(sid)
    path = "/tmp/test_serve_ckpt_churn_empty.json"
    srv.checkpoint(path)
    srv2 = DecodeServer.restore(path, cache=PlanCache())
    assert srv2.num_sessions == 0
    assert srv2.metrics_snapshot()["breakers"] == {}


def test_corrupt_and_mismatched_checkpoints_are_rejected():
    cfg = DecoderConfig(spec=SPEC)
    srv = DecodeServer(cache=PlanCache())
    srv.open_session(cfg, chunk_frames=2)
    path = "/tmp/test_serve_ckpt_bad.json"
    srv.checkpoint(path)
    raw = open(path, "rb").read()

    with pytest.raises(CheckpointError, match="cannot read"):
        DecodeServer.restore(path + ".nope")
    # tampered payload (still valid JSON) -> CRC refusal
    doc = json.loads(raw.decode())
    doc["payload"]["next_sid"] += 1
    open(path, "w").write(json.dumps(doc))
    with pytest.raises(CheckpointError, match="CRC"):
        DecodeServer.restore(path)
    # truncation -> not-JSON refusal
    open(path, "wb").write(raw[: len(raw) // 2])
    with pytest.raises(CheckpointError, match="JSON"):
        DecodeServer.restore(path)
    # schema mismatch -> cross-version refusal
    doc = json.loads(raw.decode())
    doc["schema"] = "repro.serve.checkpoint/v999"
    open(path, "w").write(json.dumps(doc))
    with pytest.raises(CheckpointError, match="schema"):
        DecodeServer.restore(path)
    # not a checkpoint at all
    open(path, "w").write("[1, 2, 3]")
    with pytest.raises(CheckpointError, match="envelope"):
        DecodeServer.restore(path)


def test_checkpoint_corrupt_fault_is_caught_at_restore():
    """The checkpoint_corrupt FaultSpec flips bytes as the file is
    written; the restore path must refuse it — and the previous good
    checkpoint (atomic replace) must still load."""
    cfg = DecoderConfig(spec=SPEC)
    path = "/tmp/test_serve_ckpt_fault.json"
    good = "/tmp/test_serve_ckpt_fault_good.json"
    faults = FaultInjector(FaultSpec("checkpoint_corrupt", after=2), seed=0)
    srv = DecodeServer(cache=PlanCache(), faults=faults)
    srv.open_session(cfg, chunk_frames=2)
    save_checkpoint(srv, good)                   # write #1: clean
    save_checkpoint(srv, path)                   # write #2: corrupted
    with pytest.raises(CheckpointError):
        DecodeServer.restore(path)
    assert DecodeServer.restore(good).num_sessions == 1


#: a checkpoint written before the kernel lost its interpret-only
#: survivor layout and bfloat16 branch metrics: its session configs still
#: carry ``layout`` and ``bm_dtype``
OLD_CKPT = os.path.join(os.path.dirname(__file__), "data",
                        "checkpoint_rate12_k7.json")


def _old_copy(tmp_path, **cfg_keys):
    """OLD_CKPT with ``cfg_keys`` set in every session's config and its
    CRC made good again."""
    import zlib
    from repro.serve.checkpoint import _canonical
    doc = json.load(open(OLD_CKPT))
    for row in doc["payload"]["sessions"]:
        row["cfg"].update(cfg_keys)
    doc["crc"] = zlib.crc32(_canonical(doc["payload"]))
    path = str(tmp_path / "old_copy.json")
    open(path, "w").write(json.dumps(doc))
    return path


def test_old_checkpoint_restores_bit_for_bit(tmp_path):
    """The file restores unedited: every session's stream state and
    undelivered bits come back as saved, and its config as saved less
    the two retired keys."""
    old = json.load(open(OLD_CKPT))["payload"]
    srv = DecodeServer.restore(OLD_CKPT, cache=PlanCache())
    new = json.load(open(srv.checkpoint(str(tmp_path / "again.json"))))
    for was, now in zip(old["sessions"], new["payload"]["sessions"]):
        assert now["ctx"] == was["ctx"] and now["ready"] == was["ready"]
        assert now["cfg"] == {k: v for k, v in was["cfg"].items()
                              if k not in ("layout", "bm_dtype")}


@pytest.mark.parametrize("layout", ["lane", "sublane"])
def test_old_checkpoint_restores_either_layout(tmp_path, layout):
    """Both survivor layouts decoded the same bits: either restores."""
    srv = DecodeServer.restore(_old_copy(tmp_path, layout=layout),
                               cache=PlanCache())
    assert srv.num_sessions == 2


def test_old_checkpoint_with_bfloat16_metrics_is_refused(tmp_path):
    """bfloat16 branch metrics were not the exact decode: such a session
    cannot be resumed bit-identically, so the restore refuses it."""
    with pytest.raises(CheckpointError, match="bfloat16"):
        DecodeServer.restore(_old_copy(tmp_path, bm_dtype="bfloat16"),
                             cache=PlanCache())


def test_new_checkpoint_has_no_retired_keys(tmp_path):
    srv = DecodeServer(cache=PlanCache())
    srv.open_session(DecoderConfig(spec=SPEC), chunk_frames=2)
    doc = json.load(open(srv.checkpoint(str(tmp_path / "new.json"))))
    (row,) = doc["payload"]["sessions"]
    assert not {"layout", "bm_dtype"} & set(row["cfg"])
    assert doc["schema"] == "repro.serve.checkpoint/v1"


def test_drain_refuses_admission_and_pushes_then_snapshots():
    cfg = DecoderConfig(spec=SPEC)
    srv = DecodeServer(slots=2, cache=PlanCache())
    sid = srv.open_session(cfg, chunk_frames=2)
    rx = _rx(6 * 64, seed=40)
    srv.push(sid, rx[: 4 * 64])
    path = "/tmp/test_serve_ckpt_drain.json"
    srv.drain(checkpoint=path)
    assert srv.metrics_snapshot()["draining"]
    with pytest.raises(Draining):
        srv.open_session(cfg, chunk_frames=2)
    with pytest.raises(Draining):
        srv.push(sid, rx[4 * 64:])
    assert srv.poll(sid).size > 0                # polls still drain out
    # the restored server admits again and resumes the stream bit-exactly
    srv2 = DecodeServer.restore(path, cache=PlanCache())
    assert not srv2.metrics_snapshot()["draining"]
    srv2.push(sid, rx[4 * 64:])
    got = np.concatenate([srv2.poll(sid), srv2.close_session(sid)])
    assert srv.poll(sid).size == 0               # nothing new on the old one
    # the checkpoint kept the undelivered bits AND the carry: the restored
    # server's output is the complete stream, bit-equal to solo decode
    want = stream_decode(cfg, rx, 6 * 64, chunk_frames=2)
    assert np.array_equal(got, want)
    srv2.metrics_snapshot()                      # still coherent


# -- circuit breaker + failover -------------------------------------------
def test_breaker_state_machine():
    br = Breaker(threshold=2, cooldown=2)
    assert not br.record_failure() and br.state == "closed"
    assert br.record_failure() and br.state == "open" and br.trips == 1
    br.step()
    assert br.state == "open"
    br.step()
    assert br.state == "half_open"
    assert br.record_failure() and br.trips == 2    # failed probe re-opens
    br.step(), br.step()
    assert br.state == "half_open"
    assert br.record_success() and br.state == "closed"
    rt = Breaker(threshold=2, cooldown=2)
    rt.load_state(br.state_dict())
    assert rt.state_dict() == br.state_dict()
    with pytest.raises(ValueError):
        rt.load_state({"state": "on fire", "consecutive": 0, "trips": 0,
                       "wait": 0})


def test_device_loss_trips_breaker_evacuates_and_recovers_bit_exact():
    """The acceptance scenario: a persistent device loss trips the
    bucket's breaker, its sessions evacuate to the reference-pinned
    failover bucket (trips/evacuated counters + health + breakers all
    say so), decoding continues bit-exactly throughout, and once the
    fault clears a half-open probe re-admits the sessions to the fast
    path."""
    cfg = DecoderConfig(spec=SPEC)
    faults = FaultInjector(FaultSpec("device_loss", after=2, count=4),
                           seed=0)
    srv = DecodeServer(slots=2, cache=PlanCache(), max_retries=2,
                       breaker_threshold=3, breaker_cooldown=2,
                       faults=faults)
    sid = srv.open_session(cfg, chunk_frames=2)
    primary = srv._sessions[sid].bucket
    n = 20 * 64
    rx = _rx(n, seed=50)
    outs, evacuated_seen, recovered = [], False, False
    for pos in range(0, n, 2 * 64):
        srv.push(sid, rx[pos: pos + 2 * 64])
        srv.step()
        outs.append(srv.poll(sid))
        b = srv._sessions[sid].bucket
        evacuated_seen |= b.pinned
        recovered |= (evacuated_seen and not b.pinned)
    outs.append(srv.close_session(sid))
    got = np.concatenate(outs)[:n]
    want = stream_decode(cfg, rx, n, chunk_frames=2)
    assert np.array_equal(got, want)
    assert evacuated_seen, "sessions never moved to the failover bucket"
    assert recovered, "sessions never came back to the fast path"
    assert primary.breaker.state == "closed"
    snap = srv.metrics_snapshot()
    t = snap["totals"]
    assert t["breaker_trips"] >= 1 and t["evacuated"] == 1
    assert t["health"] == "degraded"
    assert snap["breakers"][primary.id]["trips"] == t["breaker_trips"]
    row = next(r for r in snap["buckets"] if r["bucket"] == primary.id)
    assert row["health"] == "degraded" and row["breaker_trips"] >= 1


def test_open_breaker_routes_new_sessions_to_failover():
    cfg = DecoderConfig(spec=SPEC)
    faults = FaultInjector(FaultSpec("device_loss", after=1), seed=0)
    srv = DecodeServer(slots=2, cache=PlanCache(), max_retries=1,
                       breaker_threshold=2, breaker_cooldown=1000,
                       faults=faults)
    s1 = srv.open_session(cfg, chunk_frames=2)
    srv.push(s1, _rx(4 * 64, seed=60))
    srv.step()                                   # trips + evacuates
    assert srv._sessions[s1].bucket.pinned
    s2 = srv.open_session(cfg, chunk_frames=2)   # admitted mid-outage
    assert srv._sessions[s2].bucket.pinned       # straight to failover
    srv.close_session(s1), srv.close_session(s2)


def test_breaker_open_snapshot_keeps_trip_streak_on_late_success():
    """A launch that trips the breaker mid-retry but succeeds on a later
    attempt still fails over (the probe path re-admits) — and the open
    breaker's snapshot keeps reporting the consecutive streak that
    tripped it, not a misleading 0 from the late success."""
    cfg = DecoderConfig(spec=SPEC)
    faults = FaultInjector(FaultSpec("device_loss", after=1, count=2),
                           seed=0)
    srv = DecodeServer(slots=2, cache=PlanCache(), max_retries=2,
                       breaker_threshold=2, breaker_cooldown=1000,
                       backoff_s=0.0, faults=faults)
    sid = srv.open_session(cfg, chunk_frames=2)
    primary = srv._sessions[sid].bucket
    srv.push(sid, _rx(4 * 64, seed=84))
    srv.step()            # fail, fail (trip), late success -> evacuate
    assert srv._sessions[sid].bucket.pinned
    row = srv.metrics_snapshot()["breakers"][primary.id]
    assert row["state"] == "open"
    assert row["consecutive"] >= srv.breaker_threshold
    srv.close_session(sid)


def test_checkpoint_mid_outage_restores_evacuated_placement():
    """A checkpoint taken while a breaker is open must restore the
    breaker open AND the sessions on the failover bucket — not silently
    re-place tenants on the dead device."""
    cfg = DecoderConfig(spec=SPEC)
    faults = FaultInjector(FaultSpec("device_loss", after=1), seed=0)
    srv = DecodeServer(slots=2, cache=PlanCache(), max_retries=1,
                       breaker_threshold=2, breaker_cooldown=1000,
                       faults=faults)
    sid = srv.open_session(cfg, chunk_frames=2)
    n = 8 * 64
    rx = _rx(n, seed=61)
    srv.push(sid, rx[: 4 * 64])
    srv.step()
    assert srv._sessions[sid].bucket.pinned
    path = "/tmp/test_serve_ckpt_outage.json"
    srv.checkpoint(path)
    srv2 = DecodeServer.restore(path, cache=PlanCache())
    assert srv2._sessions[sid].bucket.pinned
    assert any(v["state"] == "open"
               for v in srv2.metrics_snapshot()["breakers"].values())
    srv2.push(sid, rx[4 * 64:])
    got = np.concatenate([srv2.poll(sid), srv2.close_session(sid)])[:n]
    want = stream_decode(cfg, rx, n, chunk_frames=2)
    assert np.array_equal(got, want)


# -- kill-restore-compare chaos -------------------------------------------
def test_kill_restore_compare_deterministic():
    """The CI chaos protocol: seeded crash_at_step kills the server
    mid-workload; the client restores from its last checkpoint, rewinds
    to the matching marker, replays — final bits of every session are
    IDENTICAL to the uninterrupted solo decode. Run twice to pin
    determinism."""
    cfg = DecoderConfig(spec=SPEC)
    n = 16 * 64
    rxs = {0: _rx(n, seed=70), 1: _rx(n, seed=71)}
    path = "/tmp/test_serve_ckpt_crash.json"

    def run():
        faults = FaultInjector(FaultSpec("crash_at_step", after=3, count=1),
                               seed=0)
        srv = DecodeServer(slots=4, cache=PlanCache(), faults=faults)
        sids = {k: srv.open_session(cfg, chunk_frames=2) for k in rxs}
        pos = {k: 0 for k in rxs}
        bits = {k: [] for k in rxs}
        mark = ({k: 0 for k in rxs}, {k: 0 for k in rxs})
        srv.checkpoint(path)
        crashes = 0
        while any(p < n for p in pos.values()):
            try:
                for k, sid in sids.items():
                    if pos[k] < n:
                        srv.push(sid, rxs[k][pos[k]: pos[k] + 2 * 64])
                        pos[k] += 2 * 64
                srv.step()
                for k, sid in sids.items():
                    bits[k].append(srv.poll(sid))
                srv.checkpoint(path)
                mark = ({k: sum(len(x) for x in bits[k]) for k in rxs},
                        dict(pos))
            except InjectedCrash:
                crashes += 1
                srv = DecodeServer.restore(path, cache=PlanCache())
                delivered, posmark = mark
                for k in rxs:
                    acc = (np.concatenate(bits[k]) if bits[k]
                           else np.zeros(0, np.int32))
                    bits[k] = [acc[: delivered[k]]]
                pos = dict(posmark)
        assert crashes == 1
        for k, sid in sids.items():
            bits[k].append(srv.close_session(sid))
        snap = srv.metrics_snapshot()
        return ({k: np.concatenate(bits[k])[:n] for k in rxs},
                snap["checkpoint"]["restores"])

    got1, restores1 = run()
    got2, restores2 = run()
    assert restores1 == restores2 == 1
    for k in rxs:
        want = stream_decode(cfg, rxs[k], n, chunk_frames=2)
        assert np.array_equal(got1[k], want), f"stream {k} diverged"
        assert np.array_equal(got2[k], got1[k]), f"run 2 not deterministic"


# -- bench-gate trajectory resilience (satellite) -------------------------
def test_trajectory_empty_stores_parse_to_no_baseline(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.trajectory import SCHEMA, load_runs
    cases = {"empty_obj.json": "{}",
             "bare_list.json": "[]",
             "empty_v2.json": json.dumps({"schema": SCHEMA, "runs": []}),
             "no_rows_v1.json": json.dumps({"schema": "kernel_sweep/v1"})}
    for name, content in cases.items():
        p = tmp_path / name
        p.write_text(content)
        assert load_runs(str(p)) == [], name
    # a bare list WITH runs is absorbed, not dropped
    p = tmp_path / "list_runs.json"
    p.write_text(json.dumps([{"rows": [], "full": False}]))
    assert load_runs(str(p)) == [{"rows": [], "full": False}]
    # structurally wrong v2 still raises (history must not vanish green)
    p = tmp_path / "bad_runs.json"
    p.write_text(json.dumps({"schema": SCHEMA, "runs": "oops"}))
    with pytest.raises(ValueError):
        load_runs(str(p))
