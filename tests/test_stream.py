"""Streaming decode front-end (core/stream.py, distributed/stream.py):
chunked decode must be bit-identical to single-shot, across backends,
chunk geometries, push raggedness, and frame sharding."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DecoderConfig, FrameSpec, STD_K7, encode,
                        make_decoder, make_stream_decoder, stream_decode)
from repro.channel.sim import awgn, bpsk

SPEC = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)


def _llr(n, rng, snr=3.0):
    bits = jnp.asarray(rng.integers(0, 2, n))
    tx = bpsk(encode(bits, STD_K7).reshape(-1))
    rx = awgn(jax.random.PRNGKey(0), tx, snr)
    return np.asarray(rx).reshape(n, 2), bits


def test_stream_equals_single_shot_ragged_pushes(rng):
    n = 5000
    llr, _ = _llr(n, rng)
    cfg = DecoderConfig(spec=SPEC)
    want = np.asarray(make_decoder(cfg)(jnp.asarray(llr), n))
    dec = make_stream_decoder(cfg, chunk_frames=5)
    got, i = [], 0
    for sz in (1, 77, 640, 64, 3000, n):             # ragged, incl. tiny
        sz = min(sz, n - i)
        got.append(dec.push(llr[i:i + sz]))
        i += sz
        if i >= n:
            break
    got.append(dec.flush())
    got = np.concatenate(got)
    assert got.shape == (n,)
    assert np.array_equal(got, want)


def test_stream_decoder_is_reusable_after_flush(rng):
    cfg = DecoderConfig(spec=SPEC)
    dec = make_stream_decoder(cfg, chunk_frames=3)
    for trial in range(2):
        n = 900 + 137 * trial                        # different tails
        llr, _ = _llr(n, np.random.default_rng(trial))
        want = np.asarray(make_decoder(cfg)(jnp.asarray(llr), n))
        got = np.concatenate([dec.push(llr), dec.flush()])
        assert np.array_equal(got, want), trial


@pytest.mark.parametrize("backend", ["kernel", "kernel_split"])
def test_stream_kernel_backends(rng, backend):
    n = 2000
    llr, _ = _llr(n, rng)
    # the split kernel has the lane layout only
    layout = "lane" if backend == "kernel_split" else "sublane"
    cfg = DecoderConfig(spec=SPEC, backend=backend, layout=layout)
    want = np.asarray(make_decoder(cfg)(jnp.asarray(llr), n))
    got = stream_decode(cfg, llr, n, chunk_frames=8)
    assert np.array_equal(got, want)


def test_stream_shorter_than_one_chunk(rng):
    n = 100                                          # < one frame even
    llr, _ = _llr(n, rng)
    cfg = DecoderConfig(spec=SPEC)
    want = np.asarray(make_decoder(cfg)(jnp.asarray(llr), n))
    dec = make_stream_decoder(cfg, chunk_frames=16)
    assert dec.push(llr).size == 0                   # nothing complete yet
    got = dec.flush()[:n]
    assert np.array_equal(got, want)


def test_default_chunk_comes_from_plan():
    """No explicit chunk_frames: the autotuner's DecodePlan sizes the
    chunk as 2 tiles x devices (double buffering geometry)."""
    from repro.kernels.autotune import plan_decode
    cfg = DecoderConfig(spec=SPEC, backend="kernel")
    dec = make_stream_decoder(cfg)
    plan = plan_decode(cfg.trellis, SPEC, pack_survivors=cfg.pack_survivors,
                       radix=cfg.radix, bm_dtype=cfg.bm_dtype,
                       layout=cfg.layout, num_devices=1)
    assert dec.chunk_frames == plan.chunk_frames == 2 * plan.frames_per_tile


def test_stream_decode_punctured_rate(rng):
    """Punctured-rate configs take the punctured symbol stream, exactly
    like make_decoder (the StreamContext depunctures in-stream)."""
    from repro.core.puncture import puncture
    n = 3024
    bits = jnp.asarray(rng.integers(0, 2, n))
    tx = bpsk(puncture(encode(bits, STD_K7), "3/4"))
    rx = np.asarray(awgn(jax.random.PRNGKey(0), tx, 6.0))
    cfg = DecoderConfig(spec=FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21),
                        rate="3/4")
    want = np.asarray(make_decoder(cfg)(jnp.asarray(rx), n))
    got = stream_decode(cfg, rx, n, chunk_frames=9)
    assert np.array_equal(got, want)
    with pytest.raises(ValueError, match="punctured"):
        stream_decode(cfg, rx)                       # n is required


PUNCTURED_SPECS = {
    "2/3": FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20),   # period 2
    "3/4": FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21),   # period 3
}


@pytest.mark.parametrize("rate", ["2/3", "3/4"])
def test_push_raw_punctured_stream_matches_framed_decode(rng, rate):
    """The depuncture-in-push satellite: raw punctured symbols pushed in
    ragged slices through StreamDecoder decode bit-identically to
    framed_decode of the same depunctured stream — no caller-side
    depuncturing, the stream-global pattern phase lives in the context."""
    from repro.core import framed_decode
    from repro.core.puncture import depuncture, puncture
    n = 3024
    bits = jnp.asarray(rng.integers(0, 2, n))
    tx = bpsk(puncture(encode(bits, STD_K7), rate))
    rx = np.asarray(awgn(jax.random.PRNGKey(1), tx, 6.0))
    spec = PUNCTURED_SPECS[rate]
    cfg = DecoderConfig(spec=spec, rate=rate)
    full = depuncture(jnp.asarray(rx), rate, n)
    want = np.asarray(framed_decode(full, STD_K7, spec, n))
    dec = make_stream_decoder(cfg, chunk_frames=7)
    got, i = [], 0
    for sz in (1, 100, 531, 2000, rx.shape[0]):       # ragged raw slices
        sz = min(sz, rx.shape[0] - i)
        got.append(dec.push(rx[i:i + sz]))
        i += sz
        if i >= rx.shape[0]:
            break
    got.append(dec.flush())
    got = np.concatenate(got)[:n]
    assert np.array_equal(got, want)


@pytest.mark.parametrize("rate", ["2/3", "3/4"])
def test_punctured_session_through_server_matches_framed_decode(rng, rate):
    """Same satellite through the serve layer: a punctured session in a
    DecodeServer returns framed_decode's bits for the depunctured
    stream."""
    from repro.core import framed_decode
    from repro.core.puncture import depuncture, puncture
    from repro.serve import DecodeServer, PlanCache
    n = 2016
    bits = jnp.asarray(rng.integers(0, 2, n))
    tx = bpsk(puncture(encode(bits, STD_K7), rate))
    rx = np.asarray(awgn(jax.random.PRNGKey(2), tx, 6.0))
    spec = PUNCTURED_SPECS[rate]
    cfg = DecoderConfig(spec=spec, rate=rate)
    want = np.asarray(framed_decode(depuncture(jnp.asarray(rx), rate, n),
                                    STD_K7, spec, n))
    srv = DecodeServer(cache=PlanCache())
    sid = srv.open_session(cfg, chunk_frames=6)
    half = rx.shape[0] // 2
    srv.push(sid, rx[:half])
    srv.step()
    srv.push(sid, rx[half:])
    got = np.concatenate([srv.poll(sid), srv.close_session(sid)])[:n]
    assert np.array_equal(got, want)


def test_punctured_flush_pads_partial_last_stage(rng):
    """A raw stream cut mid-stage still flushes: the stage whose kept
    symbols are only partly present is emitted with neutral zeros for the
    missing ones — bit-identical to depuncturing the zero-extended
    stream. (Stages whose kept symbols are ALL missing cannot exist from
    the stream's point of view: the decode is simply that much shorter.)"""
    from repro.core import framed_decode
    from repro.core.puncture import PATTERNS, depuncture
    n = 1890
    spec = PUNCTURED_SPECS["3/4"]
    cfg = DecoderConfig(spec=spec, rate="3/4")
    pat = PATTERNS["3/4"]
    m = n * pat.sum() // pat.shape[1]
    raw = rng.standard_normal(m).astype(np.float32)
    # cut inside the last 2-kept stage (phase 0): its stage emits with one
    # real symbol + one zero; the two 1-kept stages after it vanish
    cut = m - 3
    n_eff = n - 2
    ext = np.concatenate([raw[:cut], np.zeros((m - cut,), np.float32)])
    want = np.asarray(framed_decode(depuncture(jnp.asarray(ext), "3/4", n),
                                    STD_K7, spec, n))
    dec = make_stream_decoder(cfg, chunk_frames=5)
    got = np.concatenate([dec.push(raw[:cut]), dec.flush()])
    assert got.shape == (n_eff,)
    assert np.array_equal(got, want[:n_eff])


def test_stream_decoder_custom_decode_frames_memoized_per_instance(rng):
    """An explicit decode_frames override can't share the global plan
    cache (no stable identity), but the instance must still compile each
    window length exactly once — not once per dispatch."""
    from repro.core.pipeline import _build_frame_decoder
    from repro.core.stream import StreamDecoder
    n = 15 * 64
    llr, _ = _llr(n, rng)
    cfg = DecoderConfig(spec=SPEC)
    dec = StreamDecoder(cfg, 5, decode_frames=_build_frame_decoder(cfg))
    fns = set()
    got = []
    for i in range(0, n, 5 * 64):                    # 3 identical chunks
        got.append(dec.push(llr[i:i + 5 * 64]))
        fns.add(id(dec._window_decoder(5)))
    got.append(dec.flush())
    assert len(fns) == 1 and set(dec._local_fns) == {5}
    want = np.asarray(make_decoder(cfg)(jnp.asarray(llr), n))
    assert np.array_equal(np.concatenate(got), want)


def test_kernels_package_lazy_attributes():
    """repro.kernels resolves submodules on attribute access (no eager
    imports — that would re-enter repro.core mid-import)."""
    import repro.kernels as K
    assert K.ops.viterbi_decode_frames is not None
    assert K.ref.unified_decode_frames_ref is not None
    with pytest.raises(AttributeError):
        K.nonexistent_submodule


def test_sharded_frame_decoder_single_device(rng):
    from repro.distributed.stream import frame_mesh
    n = 2000
    llr, _ = _llr(n, rng)
    cfg = DecoderConfig(spec=SPEC)
    want = np.asarray(make_decoder(cfg)(jnp.asarray(llr), n))
    got = stream_decode(cfg, llr, n, chunk_frames=8, mesh=frame_mesh())
    assert np.array_equal(got, want)


SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.core import DecoderConfig, FrameSpec, STD_K7, make_decoder
from repro.core.stream import stream_decode
from repro.distributed.stream import frame_mesh

n = 4000
rng = np.random.default_rng(0)
llr = rng.standard_normal((n, 2)).astype(np.float32)
spec = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
cfg = DecoderConfig(spec=spec)
want = np.asarray(make_decoder(cfg)(jnp.asarray(llr), n))
mesh = frame_mesh()
assert mesh.devices.size == 4, mesh.devices
# chunk_frames=6 is NOT a multiple of 4 devices: exercises shard padding
got = stream_decode(cfg, llr, n, chunk_frames=6, mesh=mesh)
assert np.array_equal(got, want)
print("SHARDED_STREAM_OK")
"""


def test_sharded_stream_multi_device():
    """4 host devices: frame-sharded chunk decode == single-shot, incl.
    chunk counts that don't divide the mesh (shard padding)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SHARDED], capture_output=True,
                       text=True, timeout=600, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "SHARDED_STREAM_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
