"""Hypothesis property tests on system invariants."""
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import (FrameSpec, STD_K7, encode, framed_decode,
                        viterbi_decode)
from repro.core.trellis import make_trellis
from repro.core.puncture import PATTERNS, depuncture, puncture

settings.register_profile("ci", max_examples=15, deadline=None)
settings.load_profile("ci")


@given(st.integers(0, 2**32 - 1), st.integers(50, 400))
def test_decode_encode_roundtrip_noiseless(seed, n):
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, n)
    coded = np.asarray(encode(jnp.asarray(bits), STD_K7))
    llr = jnp.asarray(1.0 - 2.0 * coded.astype(np.float32))
    out = np.asarray(viterbi_decode(llr, STD_K7))
    assert np.array_equal(out, bits)


@given(st.integers(0, 2**32 - 1), st.integers(4, 8))
def test_random_codes_roundtrip(seed, k):
    rng = np.random.default_rng(seed)
    # random polynomials with the MSB set (delay-0 tap present)
    polys = tuple(int(rng.integers(1 << (k - 1), 1 << k)) for _ in range(2))
    tr = make_trellis(k, polys)
    bits = rng.integers(0, 2, 200)
    coded = np.asarray(encode(jnp.asarray(bits), tr))
    llr = jnp.asarray(1.0 - 2.0 * coded.astype(np.float32))
    out = np.asarray(viterbi_decode(llr, tr))
    # catastrophic codes exist among random polys; require <2% disagreement
    # only when the code is non-catastrophic (gcd of polys == 1 heuristic):
    import math
    if math.gcd(polys[0], polys[1]) == 1:
        assert np.array_equal(out, bits)


@given(st.integers(0, 2**32 - 1),
       st.sampled_from(["1/2", "2/3", "3/4"]), st.integers(24, 120))
def test_puncture_inverse_property(seed, rate, n):
    rng = np.random.default_rng(seed)
    period = PATTERNS[rate].shape[1]
    n = (n // period) * period
    x = jnp.asarray(rng.standard_normal((n, 2)).astype(np.float32))
    y = np.asarray(depuncture(puncture(x, rate), rate, n))
    mask = np.tile(PATTERNS[rate], (1, n)).T[:n].astype(bool)
    assert np.array_equal(y[mask], np.asarray(x)[mask])
    assert np.all(y[~mask] == 0)


@given(st.integers(0, 2**32 - 1),
       st.sampled_from([(False, 2), (False, 4), (True, 2), (True, 4)]),
       st.sampled_from([8, 16, "auto"]))
def test_kernel_variants_bit_identical_to_reference(seed, knobs, ft):
    """EVERY kernel configuration — packed/unpacked survivors, radix-2/4,
    any tile size — must decode random LLRs bit-identically to the
    core.decoder-based oracle."""
    from repro.core.framed import frame_llr
    from repro.kernels import ops, ref
    pack, radix = knobs
    rng = np.random.default_rng(seed)
    specs = [FrameSpec(f=64, v1=20, v2=20, f0=16, v2s=20),
             FrameSpec(f=64, v1=16, v2=21, f0=8, v2s=21),
             FrameSpec(f=96, v1=12, v2=24, f0=24, v2s=20, start="fixed")]
    spec = specs[int(rng.integers(0, len(specs)))]
    llr = jnp.asarray(rng.standard_normal((5 * spec.f, 2))
                      .astype(np.float32))          # pure noise: worst case
    frames = frame_llr(llr, spec)
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, STD_K7, spec, frames_per_tile=ft, pack_survivors=pack,
        radix=radix))
    assert np.array_equal(got, want), (spec, pack, radix, ft)


@given(st.integers(0, 2**32 - 1))
def test_stream_decode_equals_single_shot(seed):
    """Chunked streaming decode (random chunk geometry, ragged pushes) is
    bit-identical to the single-shot framed decode of the same stream."""
    from repro.core import DecoderConfig, make_decoder
    from repro.core.stream import stream_decode
    rng = np.random.default_rng(seed)
    n = int(rng.integers(300, 1200))
    spec = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
    cfg = DecoderConfig(spec=spec)
    llr = rng.standard_normal((n, 2)).astype(np.float32)
    want = np.asarray(make_decoder(cfg)(jnp.asarray(llr), n))
    got = stream_decode(cfg, llr, n,
                        chunk_frames=int(rng.integers(1, 6)),
                        push_size=int(rng.integers(1, 2 * spec.f)))
    assert np.array_equal(got, want)


@given(st.integers(0, 2**32 - 1), st.integers(50, 300))
def test_radix4_forward_bit_identical(seed, n):
    """The fused two-stage ACS is the same arithmetic: sel/sigma/amax and
    the full decode agree bit-for-bit with radix-2, odd lengths included."""
    from repro.core.decoder import viterbi_forward
    rng = np.random.default_rng(seed)
    llr = jnp.asarray(rng.standard_normal((n, 2)).astype(np.float32))
    s2, g2, a2 = viterbi_forward(llr, STD_K7)
    s4, g4, a4 = viterbi_forward(llr, STD_K7, None, 4)
    assert np.array_equal(np.asarray(s2), np.asarray(s4))
    assert np.array_equal(np.asarray(g2), np.asarray(g4))
    assert np.array_equal(np.asarray(a2), np.asarray(a4))
    assert np.array_equal(np.asarray(viterbi_decode(llr, STD_K7)),
                          np.asarray(viterbi_decode(llr, STD_K7, 4)))


@given(st.integers(0, 2**32 - 1), st.integers(1, 300))
def test_pack_roundtrip_property(seed, n):
    """The kernel's per-stage packing puts state s at bit s % 32 of word
    s // 32 of each frame's lane, for any state count."""
    from repro.kernels.packing import pack_rows, packed_width
    rng = np.random.default_rng(seed)
    sel = rng.integers(0, 2, size=(n, 4))
    words = np.concatenate([np.asarray(w)
                            for w in pack_rows(jnp.asarray(sel))])
    assert words.shape == (packed_width(n), 4)
    s = np.arange(n)
    assert np.array_equal((words[s // 32] >> (s % 32)[:, None]) & 1, sel)


@given(st.integers(0, 2**32 - 1))
def test_framed_decode_permutation_invariance(seed):
    """Decoding is per-frame independent: decoding a stream whose frames are
    decoded jointly equals the full framed decode (vmap correctness)."""
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, 512)
    coded = np.asarray(encode(jnp.asarray(bits), STD_K7))
    llr = 1 - 2 * coded.astype(np.float32)
    llr += 0.3 * rng.standard_normal(llr.shape).astype(np.float32)
    spec = FrameSpec(f=128, v1=16, v2=20)
    full = np.asarray(framed_decode(jnp.asarray(llr), STD_K7, spec))
    # decode the two halves separately at a frame boundary
    a = np.asarray(framed_decode(jnp.asarray(llr[:256 + spec.v2]),
                                 STD_K7, spec, n_out=256))[:256]
    assert np.array_equal(full[:256 - spec.v2], a[:256 - spec.v2])
