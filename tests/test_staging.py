"""Admission and staging of pushed symbols: ``StreamContext``'s stage
counts, depuncture and framing against one-shot references.

The context counts and depunctures a push by the pattern's period and
frames a window by its fixed row stride, with no per-symbol index arrays.
These tests pin that to the plain algorithms: ``puncture.depuncture`` of
the whole stream, a ``cumsum``/``searchsorted`` stage count, and an
explicit gather of each frame. Pure numpy apart from the server cases."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DecoderConfig, FrameSpec
from repro.core.puncture import PATTERNS, depuncture
from repro.core.stream import StreamContext, Window

SPECS = {
    "1/2": FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20),
    "2/3": FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20),   # period 2
    "3/4": FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21),   # period 3
}
# (rate, starting phase): every phase of each punctured pattern
PHASES = [("2/3", 0), ("2/3", 1), ("3/4", 0), ("3/4", 1), ("3/4", 2)]
SCHEDULES = ["1", "K-1", "K", "K+1", "bulk", "random"]
N_STAGES = 600                      # stages of the whole test stream


def _kept_cum(rate: str, n: int) -> np.ndarray:
    """Symbols kept by the first 1..n stages of a stream."""
    pat = PATTERNS[rate]
    return np.cumsum(pat.sum(axis=0)[np.arange(n) % pat.shape[1]])


def _complete(rate: str, r: int) -> int:
    """Complete stages the first ``r`` raw symbols fill."""
    return int(np.searchsorted(_kept_cum(rate, N_STAGES + 8), r,
                               side="right"))


def _sizes(schedule: str, rate: str, total: int, seed: int = 0) -> list:
    """Push sizes, in raw symbols, that cover ``total`` symbols."""
    K = int(PATTERNS[rate].sum())
    if schedule == "random":
        rng = np.random.default_rng(seed)
        sizes = list(rng.integers(0, 3 * K, 40)) + list(
            rng.integers(0, 400, 8))
    elif schedule == "bulk":
        sizes = [total]
    else:
        sizes = [{"1": 1, "K-1": K - 1, "K": K, "K+1": K + 1}[schedule]]
    out, left, i = [], total, 0
    while left > 0:
        sz = min(int(sizes[i % len(sizes)]), left)
        out.append(sz)
        left -= sz
        i += 1
    return out


def _stream(rate: str, seed: int = 1):
    """A raw punctured stream of N_STAGES whole stages and its one-shot
    depuncture."""
    m = int(_kept_cum(rate, N_STAGES)[-1])
    raw = np.random.default_rng(seed).standard_normal(m).astype(np.float32)
    full = np.asarray(depuncture(jnp.asarray(raw), rate, N_STAGES))
    return raw, full


def _assert_staged(ctx: StreamContext, rate: str, raw, full, r: int):
    """``ctx`` has absorbed ``raw[:r]`` and extracted no window: its
    buffer is the v1 lead plus every complete stage of the one-shot
    depuncture, its carry the symbols after them."""
    s = _complete(rate, r)
    used = int(_kept_cum(rate, N_STAGES)[s - 1]) if s else 0
    want = np.concatenate([np.zeros((ctx.spec.v1, 2), np.float32), full[:s]])
    assert ctx._buf.dtype == np.float32 and ctx._raw.dtype == np.float32
    assert np.array_equal(ctx._buf, want)
    assert np.array_equal(ctx._raw, raw[used:r])
    assert ctx._phase == s and ctx.n_in == s


@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("rate,phase", PHASES)
def test_staging_equals_one_shot_depuncture(rate, phase, schedule):
    """After every push, the buffer, raw carry, phase and stage count
    equal what the one-shot depuncture of the stream so far implies."""
    raw, full = _stream(rate)
    ctx = StreamContext(SPECS[rate], 2, 10_000, rate, sanitize="off")
    r = int(_kept_cum(rate, phase)[-1]) if phase else 0
    ctx.append(raw[:r])                          # start at ``phase``
    assert ctx._phase % PATTERNS[rate].shape[1] == phase
    for sz in _sizes(schedule, rate, raw.shape[0] - r):
        x = raw[r:r + sz].copy()
        assert ctx.append(x) == _complete(rate, r + sz) - _complete(rate, r)
        x[:] = 99.0                              # the caller reuses it
        r += sz
        _assert_staged(ctx, rate, raw, full, r)
    assert r == raw.shape[0] and ctx._raw.size == 0


@pytest.mark.parametrize("flush", ["window", "chunks"])
@pytest.mark.parametrize("rate", ["2/3", "3/4"])
def test_flush_emits_partly_filled_last_stage(rate, flush):
    """A stream cut inside a stage flushes that stage with zeros for its
    missing kept symbols, as the depuncture of the zero-extended stream
    has it; the windows carry the zero-padded end of the stream."""
    raw, _ = _stream(rate)
    spec = SPECS[rate]
    s = 301 - 301 % PATTERNS[rate].shape[1]      # a phase-0 (2-kept) stage
    cut = int(_kept_cum(rate, s)[-1]) + 1         # one of its two symbols
    ext = np.concatenate([raw[:cut], np.zeros(raw.shape[0] - cut,
                                              np.float32)])
    full = np.asarray(depuncture(jnp.asarray(ext), rate, N_STAGES))
    ctx = StreamContext(spec, 2, 2, rate)
    ctx.append(raw[:cut - 5])
    head = ctx.take_windows()
    ctx.append(raw[cut - 5:cut])
    head += ctx.take_windows()
    tail = ctx.flush_window() if flush == "window" else ctx.flush_chunks()
    tail = [tail] if flush == "window" else tail
    assert ctx.n_in == s + 1 and ctx._raw.size == 0
    lead = np.zeros((spec.v1, 2), np.float32)
    stream = np.concatenate([lead, full[:s + 1],
                             np.zeros((4 * spec.frame_len, 2), np.float32)])
    start = 0
    for w in head + tail:
        assert np.array_equal(w.window, stream[start:start + w.window.shape[0]])
        start += w.nframes * spec.f
    assert sum(w.n_bits for w in head + tail) == s + 1


@pytest.mark.parametrize("rate,phase,carry", [
    ("2/3", 1, 0), ("2/3", 0, 1), ("3/4", 1, 0), ("3/4", 2, 0),
    ("3/4", 0, 1)])
def test_state_roundtrip_mid_stream(rate, phase, carry):
    """``state_dict`` -> ``load_state`` at a non-zero phase (or with a
    raw symbol carried) restores every staging field, and both contexts
    stage the rest of the stream identically."""
    raw, full = _stream(rate)
    spec = SPECS[rate]
    r = int(_kept_cum(rate, 150 + phase)[-1]) + carry
    a = StreamContext(spec, 2, 10_000, rate)
    a.append(raw[:r])
    assert a._phase % PATTERNS[rate].shape[1] == phase
    assert a._raw.shape[0] == carry
    b = StreamContext(spec, 2, 10_000, rate)
    b.load_state(a.state_dict())
    for sz in _sizes("random", rate, raw.shape[0] - r, seed=3):
        for ctx in (a, b):
            ctx.append(raw[r:r + sz])
            _assert_staged(ctx, rate, raw, full, r + sz)
        r += sz


def test_state_bytes_are_pinned():
    """A context's state for a fixed stream is pinned, carry bytes by
    their CRC, to what the earlier index-array depuncture wrote: a
    checkpoint written before or after the period-block staging restores
    in the other bit-identically."""
    rx = np.random.default_rng(7).standard_normal(1200).astype(np.float32)
    want = {301: (225, 120, 1, 2043215199), 303: (227, 122, 0, 1855926897)}
    for cut, (phase, rows, raw_len, crc) in want.items():
        ctx = StreamContext(FrameSpec(f=63, v1=21, v2=21), 2, 2, "3/4")
        ctx.append(rx[:cut])
        ctx.take_windows()
        st = ctx.state_dict()
        assert (st["phase"], st["n_in"], st["n_out"]) == (phase, phase, 126)
        assert (st["buf_rows"], st["raw_len"], st["crc"]) == (rows, raw_len,
                                                              crc)


@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("rate", ["1/2", "2/3", "3/4"])
def test_admission_counts_what_append_stages(rate, schedule):
    """``incoming_stages`` equals the stages ``append`` then adds, and
    ``projected_windows`` the windows ``take_windows`` then yields — the
    backpressure check's count is exact, at every phase and carry."""
    ctx = StreamContext(SPECS[rate], 2, 1, rate)
    total = 4000
    rng = np.random.default_rng(5)
    pushes = [rng.standard_normal(sz).astype(np.float32)
              for sz in _sizes(schedule, rate, total)]
    if rate == "1/2":                            # whole stages of beta
        pushes = [np.concatenate([p, p]) for p in pushes]
    n_windows = 0
    for x in pushes:
        inc = ctx.incoming_stages(x)
        proj = ctx.projected_windows(inc)
        assert ctx.append(x) == inc
        taken = ctx.take_windows()
        assert len(taken) == proj
        n_windows += proj
    assert n_windows > 0


@pytest.mark.parametrize("nframes", [1, 2, 128])
def test_frames_equal_explicit_gather(nframes):
    """Frame m is rows ``[m*f, m*f + L)`` of the window, as the explicit
    gather has it; a many-frame view is read-only."""
    spec = SPECS["3/4"]
    rows = spec.v1 + nframes * spec.f + spec.v2
    win = np.random.default_rng(nframes).standard_normal(
        (rows, 2)).astype(np.float32)
    got = Window(win, nframes, nframes * spec.f).frames(spec)
    idx = (np.arange(nframes) * spec.f)[:, None] + np.arange(spec.frame_len)
    assert got.shape == (nframes, spec.frame_len, 2)
    assert np.array_equal(got, win[idx])
    if nframes > 1:
        assert not got.flags.writeable


def test_queued_frames_survive_pushes_finish_and_checkpoint(tmp_path):
    """Queued windows' frames alias the context's buffer: they keep
    their values through further pushes and the session's finish, and a
    checkpoint taken with them queued restores them bit-identically and
    decodes the same bits."""
    from repro.core.stream import stream_decode
    from repro.serve import DecodeServer, PlanCache
    rate, spec = "3/4", SPECS["3/4"]
    raw, _ = _stream(rate, seed=4)
    cfg = DecoderConfig(spec=spec, rate=rate)
    srv = DecodeServer(slots=2, cache=PlanCache())
    sid = srv.open_session(cfg, chunk_frames=2)
    cut = 4 * 2 * 63 * 4 // 3 + 7                # 4 windows and a carry
    srv.push(sid, raw[:cut])
    (bucket,) = [b for b in srv.buckets() if b.queue]
    queued = list(bucket.queue)
    assert len(queued) >= 3
    before = [w.frames.copy() for w in queued]
    path = str(tmp_path / "ckpt.json")
    srv.checkpoint(path)
    srv2 = DecodeServer.restore(path, cache=PlanCache())
    (bucket2,) = [b for b in srv2.buckets() if b.queue]
    assert [w.frames.tobytes() for w in bucket2.queue] == \
        [f.tobytes() for f in before]
    out = {}
    for name, s in (("live", srv), ("restored", srv2)):
        s.push(sid, raw[cut:cut + 500])
        s.push(sid, raw[cut + 500:])
        out[name] = np.concatenate([s.poll(sid), s.close_session(sid)])
    for w, f in zip(queued, before):             # decoded, then kept
        assert np.array_equal(w.frames, f)
    want = stream_decode(cfg, raw, N_STAGES, chunk_frames=2)
    assert np.array_equal(out["live"][:N_STAGES], want)
    assert np.array_equal(out["restored"][:N_STAGES], want)
