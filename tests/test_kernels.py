"""Kernel correctness sweeps: shapes x dtypes x codes vs the pure-jnp
oracle (ref.py), in interpret mode (CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FrameSpec, STD_K7, encode
from repro.core.framed import frame_llr
from repro.core.trellis import make_trellis
from repro.kernels import ops, ref

from conftest import noisy_llr

#: the benchmark's three trellises: GSM K=5, DVB-S K=7, UMTS K=9 rate 1/3
BENCH_CODES = [(5, (0o23, 0o33)), (7, (0o133, 0o171)),
               (9, (0o557, 0o663, 0o711))]
BENCH_IDS = ["K5", "K7", "K9"]


def _frames(bits, trellis, spec, rng, snr=3.0, dtype=np.float32):
    llr = noisy_llr(bits, trellis, snr, rng).astype(dtype)
    return frame_llr(jnp.asarray(llr), spec)


@pytest.mark.parametrize("spec", [
    FrameSpec(f=64, v1=20, v2=20),                      # serial tb
    FrameSpec(f=64, v1=20, v2=20, f0=16, v2s=20),       # parallel tb
    FrameSpec(f=64, v1=20, v2=20, f0=8, v2s=16),
    FrameSpec(f=128, v1=0, v2=32, f0=32, v2s=32),       # no left overlap
    FrameSpec(f=96, v1=12, v2=24, f0=24, v2s=20, start="fixed"),
])
def test_unified_kernel_matches_ref(rng, spec):
    bits = rng.integers(0, 2, 1000)
    frames = _frames(bits, STD_K7, spec, rng)
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(frames, STD_K7, spec))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_kernel_dtypes(rng, dtype):
    bits = rng.integers(0, 2, 400)
    spec = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
    llr = noisy_llr(bits, STD_K7, 4.0, rng)
    frames = frame_llr(jnp.asarray(llr, dtype=dtype), spec)
    want = np.asarray(ref.unified_decode_frames_ref(
        frames.astype(jnp.float32), STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(frames, STD_K7, spec))
    # bf16 quantizes the LLRs before the kernel casts up: identical inputs
    # to both paths, so outputs must match exactly
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k,polys", [(5, (0o23, 0o35)),
                                     (7, (0o171, 0o133)),
                                     (4, (0o13, 0o15, 0o17))])  # beta=3
def test_kernel_other_codes(rng, k, polys):
    tr = make_trellis(k, polys)
    bits = rng.integers(0, 2, 400)
    spec = FrameSpec(f=64, v1=16, v2=16, f0=16, v2s=16)
    frames = _frames(bits, tr, spec, rng, snr=6.0)
    want = np.asarray(ref.unified_decode_frames_ref(frames, tr, spec))
    got = np.asarray(ops.viterbi_decode_frames(frames, tr, spec))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("code", BENCH_CODES, ids=BENCH_IDS)
@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("radix", [2, 4])
def test_unified_kernel_knobs_match_ref(rng, pack, radix, code):
    """Bit-packed survivors and radix-4 ACS are bit-exact on each of the
    benchmark's trellises, including the odd-length tail paths (L odd,
    f0+v2s odd)."""
    tr = make_trellis(*code)
    bits = rng.integers(0, 2, 640)
    spec = FrameSpec(f=64, v1=20, v2=21, f0=16, v2s=21)   # f0+v2s = 37, odd
    frames = _frames(bits, tr, spec, rng)
    want = np.asarray(ref.unified_decode_frames_ref(frames, tr, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, tr, spec, pack_survivors=pack, radix=radix))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("code", BENCH_CODES, ids=BENCH_IDS)
def test_split_serial_traceback_layouts(rng, code):
    """Serial-traceback specs exercise the kernel's serial chase (one
    subframe spanning the kept region) on each benchmark trellis."""
    tr = make_trellis(*code)
    bits = rng.integers(0, 2, 400)
    spec = FrameSpec(f=64, v1=16, v2=16)                  # serial tb
    frames = _frames(bits, tr, spec, rng)
    want = np.asarray(ref.unified_decode_frames_ref(frames, tr, spec))
    got = np.asarray(ops.viterbi_decode_frames(frames, tr, spec))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k,polys", [(4, (0o13, 0o15, 0o17)),   # S=8, beta=3
                                     (5, (0o23, 0o35))])        # S=16
def test_small_state_codes_packed_sublane(rng, k, polys):
    """S < 32 states pack into one zero-padded word; the unified kernel's
    flat sublane (L*1, FT) scratch and word extraction must stay exact."""
    tr = make_trellis(k, polys)
    bits = rng.integers(0, 2, 400)
    spec = FrameSpec(f=64, v1=16, v2=16, f0=16, v2s=16)
    frames = _frames(bits, tr, spec, rng, snr=6.0)
    want = np.asarray(ref.unified_decode_frames_ref(frames, tr, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, tr, spec, pack_survivors=True, radix=4))
    assert np.array_equal(got, want)


def test_deep_tile_ft256(rng):
    """frames_per_tile >= 256: one grid step decodes the whole 256-frame
    batch as two 128-lane chunks."""
    spec = FrameSpec(f=16, v1=8, v2=12, f0=8, v2s=12)
    bits = rng.integers(0, 2, 16 * 256)
    frames = _frames(bits, STD_K7, spec, rng, snr=5.0)
    assert frames.shape[0] == 256
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, STD_K7, spec, frames_per_tile=256, pack_survivors=True,
        radix=4))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k,polys", [(7, (0o171, 0o133)),
                                     (9, (0o753, 0o561))])
def test_deep_tiles_packed_radix4(rng, k, polys):
    """frames_per_tile >= 32 (the packed-survivor headroom) stays exact for
    K=7 and K=9 — the acceptance-criteria codes."""
    tr = make_trellis(k, polys)
    bits = rng.integers(0, 2, 64 * 6)
    spec = FrameSpec(f=64, v1=16, v2=16, f0=16, v2s=16)
    frames = _frames(bits, tr, spec, rng, snr=5.0)
    want = np.asarray(ref.unified_decode_frames_ref(frames, tr, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, tr, spec, frames_per_tile=32, pack_survivors=True, radix=4))
    assert np.array_equal(got, want)


def test_auto_tile_plan_decodes(rng):
    bits = rng.integers(0, 2, 500)
    spec = FrameSpec(f=64, v1=16, v2=16, f0=16, v2s=16)
    frames = _frames(bits, STD_K7, spec, rng)
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, STD_K7, spec, frames_per_tile="auto", pack_survivors=True,
        radix=4))
    assert np.array_equal(got, want)


def test_kernel_frame_padding(rng):
    """Frame counts not divisible by the tile size are padded + unpadded."""
    bits = rng.integers(0, 2, 64 * 5)                  # 5 frames, tile=8
    spec = FrameSpec(f=64, v1=16, v2=16)
    frames = _frames(bits, STD_K7, spec, rng)
    assert frames.shape[0] == 5
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(frames, STD_K7, spec))
    assert got.shape == want.shape and np.array_equal(got, want)


@pytest.mark.parametrize("knobs,match", [
    (dict(frames_per_tile=8), "Mosaic cannot tile it"),
])
def test_compiled_request_refuses_what_mosaic_cannot_run(rng, knobs, match):
    """A compiled (interpret=False) call for a tile Mosaic cannot tile
    raises with the reason — it is never silently interpreted. The check
    runs at trace time, before anything is lowered."""
    spec = FrameSpec(f=64, v1=16, v2=16, f0=16, v2s=16)
    frames = jnp.zeros((16, spec.frame_len, 2), jnp.float32)
    with pytest.raises(ValueError, match=match):
        ops.viterbi_decode_frames(frames, STD_K7, spec, interpret=False,
                                  **knobs)


def test_platform_defaults_off_tpu():
    """Off a TPU a kernel request interprets the kernel Mosaic compiles on
    the chip; DecoderConfig keeps the reference backend (the chip's
    defaults — kernel, compiled — are exercised by chip_smoke.py)."""
    from repro.core import DecoderConfig
    from repro.obs import Tracer, set_tracer
    cfg = DecoderConfig()
    assert (cfg.backend, cfg.interpret) == ("reference", True)
    assert not hasattr(cfg, "layout") and not hasattr(cfg, "bm_dtype")
    spec = FrameSpec(f=16, v1=8, v2=8)
    t = Tracer()
    set_tracer(t)
    try:
        ops.viterbi_decode_frames(jnp.zeros((8, spec.frame_len, 2)),
                                  STD_K7, spec)
    finally:
        set_tracer(None)
    ev, = [r.attrs for r in t.spans() if r.name == "kernel_trace"]
    assert ev["interpret"] is True
    assert not {"kernel", "layout", "bm_dtype"} & set(ev)
    # the tile a compiled call would take for 8 frames: the whole batch
    from repro.kernels.autotune import tile_ok
    assert tile_ok(ev["frames_per_tile"], 8)
