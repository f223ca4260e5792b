"""Multi-tenant decode service (repro.serve): per-session bit-exactness
vs stream_decode, bucket grouping, the compiled-plan cache (one trace per
(trellis, spec, plan, nframes) bucket), admission/backpressure, and the
per-bucket metrics."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import DecoderConfig, FrameSpec, STD_K7, encode
from repro.core.puncture import puncture
from repro.core.stream import make_stream_decoder, stream_decode
from repro.core.trellis import make_trellis
from repro.channel.sim import awgn, bpsk
from repro.serve import (Backpressure, DecodeServer, PlanCache, ServerFull,
                         bucket_plan)

SPEC = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
SPEC34 = FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21)
K5 = make_trellis(5, (0o23, 0o35))


def _stream(trellis, n, rate="1/2", seed=0, snr=4.0):
    """Noisy received stream for ``trellis``: (n, 2) soft symbols, or the
    raw punctured flat stream for punctured rates."""
    rng = np.random.default_rng(seed)
    bits = jnp.asarray(rng.integers(0, 2, n))
    coded = encode(bits, trellis)
    tx = bpsk(puncture(coded, rate)) if rate != "1/2" \
        else bpsk(coded.reshape(-1))
    rx = np.asarray(awgn(jax.random.PRNGKey(seed), tx, snr))
    return rx if rate != "1/2" else rx.reshape(n, 2)


def test_server_eight_sessions_bit_exact_vs_stream_decode():
    """The acceptance criterion: >= 8 concurrent sessions across distinct
    code configs (different K AND a punctured rate), ragged interleaved
    pushes, every session's bits identical to running it alone through
    stream_decode — with exactly one plan-cache trace per (trellis, spec,
    plan, nframes) bucket shape."""
    cfgs = [DecoderConfig(spec=SPEC),                  # K7 rate 1/2
            DecoderConfig(spec=SPEC34, rate="3/4"),    # K7 punctured
            DecoderConfig(trellis=K5, spec=SPEC)]      # K5 rate 1/2
    cache = PlanCache()
    srv = DecodeServer(slots=3, queue_depth=4, cache=cache)
    data = []
    for i in range(8):
        cfg = cfgs[i % 3]
        n = 1800 + 137 * i
        llr = _stream(cfg.trellis, n, cfg.rate, seed=i)
        sid = srv.open_session(cfg, chunk_frames=5)
        data.append((sid, cfg, llr, n))
    assert len({s.bucket.id for s in srv._sessions.values()}) == 3

    pos = [0] * len(data)
    sizes = (311, 1000, 97, 1200)      # ragged; <= queue_depth chunks each
    outs = {sid: [] for sid, _, _, _ in data}
    rnd, done = 0, False
    while not done:
        done = True
        for j, (sid, cfg, llr, n) in enumerate(data):
            if pos[j] >= llr.shape[0]:
                continue
            done = False
            sz = sizes[(j + rnd) % len(sizes)]
            try:
                srv.push(sid, llr[pos[j]:pos[j] + sz])
                pos[j] += sz
            except Backpressure:
                srv.step()
        srv.step()
        for sid, _, _, _ in data:
            outs[sid].append(srv.poll(sid))            # non-blocking
        rnd += 1
    for sid, cfg, llr, n in data:
        outs[sid].append(srv.close_session(sid))
        got = np.concatenate(outs[sid])[:n]
        want = stream_decode(cfg, llr, n, chunk_frames=5)
        assert np.array_equal(got, want), f"session {sid} diverged"
    stats = cache.stats()
    # one trace per distinct (bucket, batch shape); every re-use is a hit
    assert stats["traces"] == stats["misses"] - 3      # 3 frame closures
    assert stats["hits"] > stats["misses"]
    assert srv.num_sessions == 0


def test_one_compile_per_bucket_under_churn():
    """Tenant churn: generations of sessions of one config open, decode,
    and close — the trace count stops at one per batch shape (the full
    2-slot launch and the 1-window close drain), no matter how many
    sessions come and go."""
    cfg = DecoderConfig(spec=SPEC)
    cache = PlanCache()
    srv = DecodeServer(slots=2, cache=cache)
    C, n = 4, 4 * 64
    want = None
    for gen in range(3):
        sids = [srv.open_session(cfg, chunk_frames=C) for _ in range(2)]
        llr = _stream(STD_K7, n + SPEC.v2, seed=0)     # one FULL window
        for sid in sids:
            srv.push(sid, llr)
        assert srv.step() == 2                         # one 2-window launch
        for sid in sids:
            got = np.concatenate([srv.poll(sid), srv.close_session(sid)])
            if want is None:
                want = stream_decode(cfg, llr, n + SPEC.v2, chunk_frames=C)
            assert np.array_equal(got[:n + SPEC.v2], want)
        assert srv.num_sessions == 0
    stats = cache.stats()
    assert stats["traces"] == 2                        # B=2C and B=C shapes
    assert stats["misses"] == 3                        # + the frame closure
    assert stats["hits"] >= 3 * 3 - 2


def test_plan_cache_shared_across_stream_decoders():
    """Two StreamDecoders of the same cfg share one compiled window fn —
    tenant churn at the stream layer never re-traces."""
    cfg = DecoderConfig(spec=SPEC)
    cache = PlanCache()
    llr = _stream(STD_K7, 9 * 64, seed=3)   # one 5-frame chunk + 4-frame tail
    outs = []
    for _ in range(3):
        dec = make_stream_decoder(cfg, chunk_frames=5, cache=cache)
        outs.append(np.concatenate([dec.push(llr), dec.flush()]))
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])
    stats = cache.stats()
    assert stats["traces"] == 2                        # chunk fn + tail fn
    assert stats["hits"] >= 4


def test_punctured_sessions_share_bucket_with_rate_half():
    """Rate is NOT part of the bucket key: a rate-1/2 and a rate-3/4
    session of the same trellis/spec decode in the same bucket (the 3/4
    session depunctures per-session, upstream of the batch)."""
    spec = FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21)
    c12 = DecoderConfig(spec=spec)
    c34 = DecoderConfig(spec=spec, rate="3/4")
    srv = DecodeServer(slots=2, cache=PlanCache())
    n = 1890
    s12 = srv.open_session(c12, chunk_frames=4)
    s34 = srv.open_session(c34, chunk_frames=4)
    assert len(srv.buckets()) == 1
    llr12 = _stream(STD_K7, n, seed=11)
    raw34 = _stream(STD_K7, n, "3/4", seed=12)
    srv.push(s12, llr12)
    srv.push(s34, raw34)
    srv.drain()
    got12 = np.concatenate([srv.poll(s12), srv.close_session(s12)])[:n]
    got34 = np.concatenate([srv.poll(s34), srv.close_session(s34)])[:n]
    assert np.array_equal(got12, stream_decode(c12, llr12, n, chunk_frames=4))
    assert np.array_equal(got34, stream_decode(c34, raw34, n, chunk_frames=4))


def test_admission_control():
    srv = DecodeServer(max_sessions=2, cache=PlanCache())
    cfg = DecoderConfig(spec=SPEC)
    a = srv.open_session(cfg)
    srv.open_session(cfg)
    with pytest.raises(ServerFull, match="max_sessions"):
        srv.open_session(cfg)
    srv.close_session(a)                               # freeing re-admits
    srv.open_session(cfg)


def test_close_session_tail_longer_than_one_chunk():
    """Regression: a session whose final tail exceeds one chunk (the last
    chunk was only missing v2 right-context stages) must not lose bits —
    flush_chunks splits the tail across full-chunk windows."""
    cfg = DecoderConfig(spec=SPEC)
    srv = DecodeServer(cache=PlanCache())
    n = 330                            # chunk covers 320; tail = 330 > 320
    llr = _stream(STD_K7, n, seed=31)
    sid = srv.open_session(cfg, chunk_frames=5)
    srv.push(sid, llr)
    assert srv._session(sid).inflight == 0             # no complete window
    got = srv.close_session(sid)
    assert got.shape == (n,)
    assert np.array_equal(got, stream_decode(cfg, llr, n, chunk_frames=5))


def test_push_larger_than_queue_depth_raises_before_absorbing():
    """A single push worth more than queue_depth windows is refused UP
    FRONT (retry-safe: nothing was absorbed), and the same data split
    into smaller pushes goes through."""
    cfg = DecoderConfig(spec=SPEC)
    srv = DecodeServer(queue_depth=2, slots=8, cache=PlanCache())
    sid = srv.open_session(cfg, chunk_frames=2)
    n = 10 * 128                       # ~10 windows at 2-frame chunks
    llr = _stream(STD_K7, n, seed=17)
    with pytest.raises(Backpressure, match="split"):
        srv.push(sid, llr)
    assert srv._session(sid).inflight == 0
    for i in range(0, n, 128):         # one chunk at a time, stepping
        srv.push(sid, llr[i:i + 128])
        srv.step()
    got = np.concatenate([srv.poll(sid), srv.close_session(sid)])[:n]
    assert np.array_equal(got, stream_decode(cfg, llr, n, chunk_frames=2))


def test_backpressure_and_recovery():
    srv = DecodeServer(queue_depth=2, slots=8, cache=PlanCache())
    cfg = DecoderConfig(spec=SPEC)
    sid = srv.open_session(cfg, chunk_frames=2)
    chunk = _stream(STD_K7, 2 * 64 + SPEC.v2, seed=5)  # 1+ window per push
    srv.push(sid, chunk)
    srv.push(sid, chunk)
    with pytest.raises(Backpressure, match="step"):
        srv.push(sid, chunk)
    srv.step()                                         # drains the queue
    srv.push(sid, chunk)                               # accepted again
    srv.close_session(sid)


def test_unknown_session_errors():
    srv = DecodeServer(cache=PlanCache())
    with pytest.raises(KeyError, match="no live session"):
        srv.push(99, np.zeros((4, 2), np.float32))
    with pytest.raises(KeyError, match="no live session"):
        srv.poll(99)


def test_session_shorter_than_one_chunk():
    """A stream smaller than one chunk decodes entirely via the padded
    flush window."""
    cfg = DecoderConfig(spec=SPEC)
    srv = DecodeServer(cache=PlanCache())
    n = 100                                            # < one frame even
    llr = _stream(STD_K7, n, seed=7)
    sid = srv.open_session(cfg, chunk_frames=16)
    srv.push(sid, llr)
    assert srv.poll(sid).size == 0                     # nothing complete
    got = srv.close_session(sid)[:n]
    assert np.array_equal(got, stream_decode(cfg, llr, n, chunk_frames=16))


def test_metrics_occupancy_and_latency():
    """One session in a 4-slot bucket: every launch carries 1 window of
    C frames; with the kernel backend the tile padding is charged, with
    the reference backend occupancy is 1.0 by definition. Latency
    percentiles are ordered and positive."""
    cfg = DecoderConfig(spec=SPEC)
    srv = DecodeServer(slots=4, cache=PlanCache())
    sid = srv.open_session(cfg, chunk_frames=4)
    llr = _stream(STD_K7, 16 * 64, seed=9)
    srv.push(sid, llr)
    srv.drain()
    srv.close_session(sid)
    snap = srv.metrics_snapshot()
    assert len(snap["buckets"]) == 1
    row = snap["buckets"][0]
    assert row["launches"] == 2 and row["windows"] == 4   # 3 full + tail
    assert row["occupancy"] == 1.0                        # reference: no pad
    assert 0 < row["p50_ms"] <= row["p99_ms"]
    assert snap["totals"]["bits"] == row["bits"] == 16 * 64
    assert snap["plan_cache"]["traces"] >= 1


def test_kernel_backend_bucket_counts_tile_padding():
    """Kernel-backend buckets charge the ops-level tile padding to
    occupancy: a single 2-frame-chunk session under an 8-frame tile plan
    decodes 6 padding frames per launch."""
    cfg = DecoderConfig(spec=SPEC, backend="kernel", frames_per_tile=8)
    srv = DecodeServer(slots=1, cache=PlanCache())
    sid = srv.open_session(cfg, chunk_frames=2)
    plan = bucket_plan(cfg, chunk_frames=2)
    assert plan.frames_per_tile == 8
    llr = _stream(STD_K7, 4 * 64, seed=13)
    srv.push(sid, llr)
    srv.drain()
    got = np.concatenate([srv.poll(sid), srv.close_session(sid)])
    want = stream_decode(cfg, llr, 4 * 64, chunk_frames=2)
    assert np.array_equal(got, want)
    row = srv.metrics_snapshot()["buckets"][0]
    assert row["pad_frames"] == row["launches"] * 6
    assert row["occupancy"] == pytest.approx(2 / 8)


def test_server_sharded_mesh_single_device():
    """mesh= routes bucket batches through the sharded frame decoder."""
    from repro.distributed.stream import frame_mesh
    cfg = DecoderConfig(spec=SPEC)
    srv = DecodeServer(slots=2, mesh=frame_mesh(), cache=PlanCache())
    n = 1500
    llr = _stream(STD_K7, n, seed=21)
    sid = srv.open_session(cfg, chunk_frames=6)
    srv.push(sid, llr)
    got = np.concatenate([srv.poll(sid), srv.close_session(sid)])[:n]
    assert np.array_equal(got, stream_decode(cfg, llr, n, chunk_frames=6))


def test_bucket_plan_matches_stream_default():
    """A session admitted without chunk_frames buckets under the same
    plan_decode geometry the single-stream front-end uses."""
    from repro.kernels.autotune import plan_decode
    cfg = DecoderConfig(spec=SPEC, backend="kernel")
    plan = bucket_plan(cfg)
    want = plan_decode(STD_K7, SPEC, pack_survivors=cfg.pack_survivors,
                       radix=cfg.radix, num_devices=1)
    assert plan.cache_key() == want.cache_key()
    assert plan.fingerprint() == want.fingerprint()
    assert len(plan.fingerprint()) == 10
