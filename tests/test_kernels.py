"""Per-kernel correctness sweeps: shapes x dtypes x codes vs the pure-jnp
oracle (ref.py), in interpret mode (CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FrameSpec, STD_K7, encode
from repro.core.framed import frame_llr
from repro.core.trellis import make_trellis
from repro.kernels import ops, ref

from conftest import noisy_llr


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
    got = np.asarray(ops.viterbi_decode_frames(frames, STD_K7, spec,
                                               unified=True))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("spec", [
    FrameSpec(f=64, v1=20, v2=20),
    FrameSpec(f=64, v1=20, v2=20, f0=16, v2s=20),
])
def test_split_kernel_matches_ref(rng, spec):
    bits = rng.integers(0, 2, 600)
    frames = _frames(bits, STD_K7, spec, rng)
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(frames, STD_K7, spec,
                                               unified=False))
    assert np.array_equal(got, want)


def test_forward_kernel_matches_ref(rng):
    bits = rng.integers(0, 2, 500)
    spec = FrameSpec(f=64, v1=16, v2=16)
    frames = _frames(bits, STD_K7, spec, rng)
    from repro.kernels.viterbi_fwd import forward_frames
    F = frames.shape[0]
    Fp = -(-F // 8) * 8
    padded = jnp.pad(frames, ((0, Fp - F), (0, 0), (0, 0)))
    sel, amax = forward_frames(padded, trellis=STD_K7)
    sel_w, amax_w = ref.forward_frames_ref(padded, STD_K7)
    assert np.array_equal(np.asarray(sel), np.asarray(sel_w))
    assert np.array_equal(np.asarray(amax), np.asarray(amax_w))


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


@pytest.mark.parametrize("layout", ["lane", "sublane"])
@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("radix", [2, 4])
def test_unified_kernel_knobs_match_ref(rng, pack, radix, layout):
    """Bit-packed survivors, radix-4 ACS, and both memory layouts are
    bit-exact, including the odd-length tail paths (L odd, f0+v2s odd)."""
    bits = rng.integers(0, 2, 640)
    spec = FrameSpec(f=64, v1=20, v2=21, f0=16, v2s=21)   # f0+v2s = 37, odd
    frames = _frames(bits, STD_K7, spec, rng)
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, STD_K7, spec, unified=True, pack_survivors=pack, radix=radix,
        layout=layout))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("radix", [2, 4])
def test_split_kernel_knobs_match_ref(rng, pack, radix):
    """The split path streams (possibly packed) survivors through HBM and
    traces back at the JAX level — same bits for every knob combo."""
    bits = rng.integers(0, 2, 600)
    spec = FrameSpec(f=64, v1=20, v2=20, f0=16, v2s=20)
    frames = _frames(bits, STD_K7, spec, rng)
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, STD_K7, spec, unified=False, pack_survivors=pack,
        radix=radix, layout="lane"))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("ft", ["auto", 16])   # planner / kernel refuse
def test_split_kernel_refuses_sublane_layout(ft):
    """The split kernel has the lane layout only; a sublane request raises
    instead of decoding in another layout, whether the planner sizes the
    tile or the caller does."""
    spec = FrameSpec(f=64, v1=16, v2=16, f0=16, v2s=16)
    frames = jnp.zeros((16, spec.frame_len, 2), jnp.float32)
    with pytest.raises(ValueError, match="only in the lane layout"):
        ops.viterbi_decode_frames(frames, STD_K7, spec, unified=False,
                                  frames_per_tile=ft, layout="sublane",
                                  interpret=True)


@pytest.mark.parametrize("unified,layout", [(True, "lane"),
                                            (True, "sublane"),
                                            (False, "lane")])
def test_split_serial_traceback_layouts(rng, unified, layout):
    """Serial-traceback specs exercise the batched serial chase of the
    split path and the unified kernel's serial chase in both layouts."""
    bits = rng.integers(0, 2, 400)
    spec = FrameSpec(f=64, v1=16, v2=16)                  # serial tb
    frames = _frames(bits, STD_K7, spec, rng)
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, STD_K7, spec, unified=unified, layout=layout))
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
        frames, tr, spec, pack_survivors=True, radix=4, layout="sublane"))
    assert np.array_equal(got, want)


def test_deep_tile_ft256(rng):
    """frames_per_tile >= 256 (beyond PR-1's exercised range): one grid
    step decodes the whole 256-frame batch in the sublane layout."""
    spec = FrameSpec(f=16, v1=8, v2=12, f0=8, v2s=12)
    bits = rng.integers(0, 2, 16 * 256)
    frames = _frames(bits, STD_K7, spec, rng, snr=5.0)
    assert frames.shape[0] == 256
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, STD_K7, spec, frames_per_tile=256, pack_survivors=True,
        radix=4, layout="sublane"))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("layout", ["lane", "sublane"])
def test_bf16_branch_metrics_decode(rng, layout):
    """bf16 branch metrics are not bit-exact, but at a clean SNR the
    decoded bits must still round-trip, and the knob must work on both
    kernels and layouts (test_ber.py bounds the noisy-channel BER delta;
    the split kernel has the lane layout only)."""
    bits = rng.integers(0, 2, 640)
    spec = FrameSpec(f=64, v1=20, v2=20, f0=16, v2s=20)
    frames = _frames(bits, STD_K7, spec, rng, snr=8.0)
    for unified in (True, False) if layout == "lane" else (True,):
        got = np.asarray(ops.viterbi_decode_frames(
            frames, STD_K7, spec, unified=unified, layout=layout,
            bm_dtype="bfloat16"))
        decoded = got.reshape(-1)[:len(bits)]
        assert (decoded != bits).mean() == 0.0, (unified, layout)


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


def test_forward_kernel_packed_stream(rng):
    """Packed split-kernel survivors == pack_bits(unpacked oracle sel)."""
    from repro.kernels.packing import pack_bits
    from repro.kernels.viterbi_fwd import forward_frames
    bits = rng.integers(0, 2, 500)
    spec = FrameSpec(f=64, v1=16, v2=16)
    frames = _frames(bits, STD_K7, spec, rng)
    Fp = -(-frames.shape[0] // 8) * 8
    padded = jnp.pad(frames, ((0, Fp - frames.shape[0]), (0, 0), (0, 0)))
    sel, amax = forward_frames(padded, trellis=STD_K7, pack_survivors=True)
    sel_w, amax_w = ref.forward_frames_ref(padded, STD_K7)
    assert sel.shape == (Fp, spec.frame_len, 2)      # S=64 -> 2 words
    assert np.array_equal(np.asarray(sel), np.asarray(pack_bits(sel_w)))
    assert np.array_equal(np.asarray(amax), np.asarray(amax_w))


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
    (dict(layout="lane"), "layout='lane' runs only in interpret mode"),
    (dict(layout="sublane", bm_dtype="bfloat16"),
     "bm_dtype='bfloat16' runs only in interpret mode"),
    (dict(layout="sublane", unified=False), "split kernel"),
    (dict(layout="sublane", frames_per_tile=8), "Mosaic cannot tile it"),
])
def test_compiled_request_refuses_what_mosaic_cannot_run(rng, knobs, match):
    """A compiled (interpret=False) call for a knob outside the compiled
    path raises with the reason — it is never silently interpreted. The
    checks run at trace time, before anything is lowered."""
    spec = FrameSpec(f=64, v1=16, v2=16, f0=16, v2s=16)
    frames = jnp.zeros((16, spec.frame_len, 2), jnp.float32)
    with pytest.raises(ValueError, match=match):
        ops.viterbi_decode_frames(frames, STD_K7, spec, interpret=False,
                                  **knobs)


def test_platform_defaults_off_tpu():
    """Off a TPU the kernel entry point keeps interpret mode and the lane
    layout; DecoderConfig keeps the reference backend (the chip's
    defaults — kernel, compiled, sublane — are exercised by
    chip_smoke.py)."""
    from repro.core import DecoderConfig
    from repro.obs import Tracer, set_tracer
    cfg = DecoderConfig()
    assert (cfg.backend, cfg.interpret, cfg.layout) == \
        ("reference", True, "lane")
    spec = FrameSpec(f=16, v1=8, v2=8)
    t = Tracer()
    set_tracer(t)
    try:
        ops.viterbi_decode_frames(jnp.zeros((8, spec.frame_len, 2)),
                                  STD_K7, spec)
    finally:
        set_tracer(None)
    ev, = [r.attrs for r in t.spans() if r.name == "kernel_trace"]
    assert ev["interpret"] is True and ev["layout"] == "lane"
