"""Intra-frame block-parallel decode (kernels/block.py + the stack above).

The two exactness regimes anchor everything (see kernels/block.py):

* fine-framing equivalence — ``overlap <= min(v1, v2)``: blocking the
  frames of ``spec`` is bit-identical to framing the stream directly
  with ``spec.blocked(B, overlap)``, because every block window lies
  inside its frame's real data;
* degenerate full-overlap — ``overlap >= full_overlap(spec, B)``: every
  block window covers the whole frame, so the blocked decode is
  bit-identical to the unblocked one.

Between the regimes, blocking is the truncated-traceback approximation:
gated here against the exact decode at 1e-3 BER, with the default overlap
~5 constraint lengths.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import noisy_llr
from repro.core import (DecoderConfig, FrameSpec, STD_K7, make_decoder,
                        make_trellis)
from repro.core.decoder import viterbi_decode
from repro.core.framed import frame_llr, merge_blocks, reframe_blocks
from repro.core.stream import stream_decode
from repro.kernels import ops
from repro.kernels.autotune import plan_decode
from repro.kernels.block import (BLOCK_LEN_THRESHOLD, choose_block_frames,
                                 default_overlap, full_overlap,
                                 resolve_block)

SERIAL = FrameSpec(f=256, v1=20, v2=20)
# v2s <= overlap <= min(v1, v2) must be satisfiable for the fine-framing
# regime to include a parallel-traceback geometry
PARALLEL = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=12)


def _frames(spec, n, rng, beta=2):
    llr = rng.standard_normal((n, beta)).astype(np.float32)
    return jnp.asarray(llr), frame_llr(jnp.asarray(llr), spec)


# -- geometry -------------------------------------------------------------
def test_blocked_spec_geometry():
    sub = SERIAL.blocked(4, 16)
    assert (sub.f, sub.v1, sub.v2) == (64, 16, 16)
    assert not sub.parallel_tb
    subp = PARALLEL.blocked(2, 16)
    assert (subp.f, subp.f0, subp.v2s) == (32, 16, 12)
    assert subp.parallel_tb


def test_blocked_spec_validation_errors():
    with pytest.raises(ValueError, match="not a multiple of block_frames"):
        SERIAL.blocked(3, 10)
    with pytest.raises(ValueError, match="overlap must be >= 0"):
        SERIAL.blocked(4, -1)
    with pytest.raises(ValueError, match="not a multiple of f0"):
        PARALLEL.blocked(8, 12)               # fb=8 not divisible by f0=16
    with pytest.raises(ValueError, match="exceeds the block overlap"):
        PARALLEL.blocked(2, 8)                # v2s=12 > ov=8


def test_reframe_blocks_matches_fine_framing(rng):
    """ov <= min(v1, v2): block windows ARE the fine framing's windows."""
    llr, frames = _frames(SERIAL, 8 * SERIAL.f, rng)
    blocks = reframe_blocks(frames, SERIAL, 4, 16)
    fine = frame_llr(llr, SERIAL.blocked(4, 16))
    assert blocks.shape == fine.shape
    assert np.array_equal(np.asarray(blocks), np.asarray(fine))


def test_merge_blocks_inverts_reframe_shape():
    bits = jnp.arange(8 * 64, dtype=jnp.int32).reshape(8, 64)
    merged = merge_blocks(bits, 4)
    assert merged.shape == (2, 256)
    assert np.array_equal(np.asarray(merged).reshape(-1),
                          np.asarray(bits).reshape(-1))


# -- policy ---------------------------------------------------------------
def test_default_overlap_is_5K_and_covers_v2s():
    assert default_overlap(STD_K7) == 5 * STD_K7.k
    wide = FrameSpec(f=4096, v1=64, v2=64, f0=64, v2s=40)
    assert default_overlap(STD_K7, wide) == 40
    assert default_overlap(STD_K7, PARALLEL) == 35


def test_resolve_block_auto_policy():
    short = FrameSpec(f=256, v1=20, v2=20)
    assert short.f < BLOCK_LEN_THRESHOLD
    assert resolve_block(STD_K7, short, "auto") == (1, 0)
    long = FrameSpec(f=4096, v1=32, v2=32, f0=32, v2s=32)
    bf, ov = resolve_block(STD_K7, long, "auto")
    assert bf > 1 and ov == 35
    fb = long.f // bf
    assert fb >= 2 * ov and fb % long.f0 == 0
    assert bf == choose_block_frames(long, ov)
    # explicit knobs pass through (validated), 1/None/0 mean off
    assert resolve_block(STD_K7, long, 8, 40) == (8, 40)
    for off in (1, None, 0):
        assert resolve_block(STD_K7, long, off) == (1, 0)
    with pytest.raises(ValueError, match="not a multiple"):
        resolve_block(STD_K7, long, 3)


def test_full_overlap_value():
    assert full_overlap(SERIAL, 4) == 3 * 64 + 20
    with pytest.raises(ValueError, match="not a multiple"):
        full_overlap(SERIAL, 3)


# -- kernel-path exactness ------------------------------------------------
@pytest.mark.parametrize("code", [(5, (0o23, 0o33)), (7, (0o133, 0o171)),
                                  (9, (0o557, 0o663, 0o711))],
                         ids=["K5", "K7", "K9"])
@pytest.mark.parametrize("pack", [False, True])
def test_kernel_fine_framing_equivalence(code, pack, rng):
    """Blocked kernel decode == the same kernel decoding the fine framing
    directly, per trellis of the benchmark and packing (the survivor
    machinery is reused unchanged by blocks)."""
    tr = make_trellis(*code)
    llr, frames = _frames(SERIAL, 8 * SERIAL.f, rng, tr.beta)
    blocked = ops.viterbi_decode_frames(
        frames, tr, SERIAL, block_frames=4, overlap=16,
        pack_survivors=pack)
    fine = ops.viterbi_decode_frames(
        frame_llr(llr, SERIAL.blocked(4, 16)), tr, SERIAL.blocked(4, 16),
        pack_survivors=pack)
    assert blocked.shape == (8, SERIAL.f)
    assert np.array_equal(np.asarray(blocked).reshape(-1),
                          np.asarray(fine).reshape(-1))


@pytest.mark.parametrize("spec", [FrameSpec(f=64, v1=16, v2=20),
                                  FrameSpec(f=64, v1=16, v2=20,
                                            f0=16, v2s=20)],
                         ids=["serial", "parallel_tb"])
@pytest.mark.parametrize("B", [2, 4])
def test_kernel_degenerate_full_overlap_bit_identity(spec, B, rng):
    """overlap >= full_overlap: blocking must change NOTHING."""
    _, frames = _frames(spec, 8 * spec.f, rng)
    ov = full_overlap(spec, B)
    plain = ops.viterbi_decode_frames(frames, STD_K7, spec)
    blocked = ops.viterbi_decode_frames(frames, STD_K7, spec,
                                        block_frames=B, overlap=ov)
    assert np.array_equal(np.asarray(plain), np.asarray(blocked))


@pytest.mark.parametrize("backend", ["kernel"])
def test_blocked_backends_match_blocked_reference(backend, rng):
    """Both backends apply the SAME decomposition — bit-identical under
    blocking, so serve degrade/failover to reference is safe."""
    spec = FrameSpec(f=128, v1=16, v2=20)
    n = 4 * spec.f
    llr = jnp.asarray(rng.standard_normal((n, 2)).astype(np.float32))
    kw = dict(spec=spec, block_frames=4, overlap=24)
    want = make_decoder(DecoderConfig(**kw))(llr, n)
    got = make_decoder(DecoderConfig(backend=backend, **kw))(llr, n)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# -- accuracy -------------------------------------------------------------
@pytest.mark.parametrize("snr_db", [2.0, 3.0])
def test_block_ber_within_gate_of_exact(snr_db, rng):
    """The truncated-traceback approximation at the ~5K default overlap
    stays within 1e-3 BER of the EXACT (unframed) Viterbi decode at the
    gated SNR points — the bf16 gating pattern of tests/test_ber.py."""
    spec = FrameSpec(f=4096, v1=32, v2=32, f0=32, v2s=32)
    n = 8 * spec.f
    bits = rng.integers(0, 2, n).astype(np.int32)
    llr = noisy_llr(bits, STD_K7, snr_db, rng)
    exact = np.asarray(viterbi_decode(jnp.asarray(llr), STD_K7))
    ber_exact = float(np.mean(exact != bits))
    dec = make_decoder(DecoderConfig(spec=spec, block_frames="auto"))
    got = np.asarray(dec(jnp.asarray(llr), n))
    ber_blk = float(np.mean(got != bits))
    assert abs(ber_blk - ber_exact) < 1e-3, (ber_blk, ber_exact)


# -- streaming / planning / serve ----------------------------------------
def test_stream_decode_blocked_matches_single_shot(rng):
    spec = FrameSpec(f=2048, v1=32, v2=32)
    cfg = DecoderConfig(spec=spec, backend="kernel", block_frames="auto")
    n = 3 * spec.f
    bits = rng.integers(0, 2, n).astype(np.int32)
    llr = noisy_llr(bits, STD_K7, 3.0, rng)
    one = np.asarray(make_decoder(cfg)(jnp.asarray(llr), n))
    st = stream_decode(cfg, llr, n, chunk_frames=2)
    assert np.array_equal(one, st)


def test_plan_decode_block_roundtrip(rng):
    """plan_decode resolves the auto policy, budgets the tile against the
    derived block spec (frames_per_tile counts blocks), keeps chunk_frames
    in outer frames, and kernel_kwargs() drives the kernel directly."""
    spec = FrameSpec(f=4096, v1=32, v2=32, f0=32, v2s=32)
    seq = plan_decode(STD_K7, spec)
    blk = plan_decode(STD_K7, spec, block_frames="auto")
    assert blk.block_frames > 1 and blk.overlap == 35
    assert blk.frames_per_tile > seq.frames_per_tile
    assert blk.cache_key() != seq.cache_key()
    assert blk.chunk_frames >= 1
    kw = blk.kernel_kwargs()
    assert kw["block_frames"] == blk.block_frames
    assert kw["overlap"] == blk.overlap
    _, frames = _frames(spec, 2 * spec.f, rng)
    bits = ops.viterbi_decode_frames(frames, STD_K7, spec, **kw)
    assert bits.shape == (2, spec.f)


def test_decoder_config_validates_block_knobs():
    with pytest.raises(ValueError, match="not a multiple"):
        DecoderConfig(spec=SERIAL, block_frames=3)
    with pytest.raises(ValueError, match="block_frames must be"):
        DecoderConfig(spec=SERIAL, block_frames="sometimes")
    with pytest.raises(ValueError, match="overlap must be"):
        DecoderConfig(spec=SERIAL, overlap=-1)
    DecoderConfig(spec=SERIAL, block_frames="auto")    # sane configs pass
    DecoderConfig(spec=SERIAL, block_frames=4, overlap=16)


def test_serve_low_latency_session(rng):
    """open_session(low_latency=True) engages the auto block policy: the
    session lands in its own bucket (plan identity includes the block
    knobs), decodes on a blocked plan, and returns exactly the bits of
    the equivalent blocked stream_decode."""
    from repro.serve import DecodeServer, PlanCache
    import dataclasses
    spec = FrameSpec(f=2048, v1=32, v2=32)
    cfg = DecoderConfig(spec=spec, backend="kernel")
    n = 2 * spec.f
    bits = rng.integers(0, 2, n).astype(np.int32)
    llr = noisy_llr(bits, STD_K7, 3.0, rng)

    srv = DecodeServer(cache=PlanCache())
    sid_ll = srv.open_session(cfg, chunk_frames=1, low_latency=True)
    sid_seq = srv.open_session(cfg, chunk_frames=1)
    buckets = {s.bucket.id for s in srv._sessions.values()}
    assert len(buckets) == 2, "low-latency session must bucket separately"
    ll_bucket = srv._sessions[sid_ll].bucket
    assert ll_bucket.plan.block_frames > 1
    assert ll_bucket.decode_cfg.block_frames == "auto"
    for sid in (sid_ll, sid_seq):
        srv.push(sid, llr)
        while srv.step():
            pass
    got_ll = np.concatenate([srv.poll(sid_ll), srv.close_session(sid_ll)])[:n]
    got_seq = np.concatenate([srv.poll(sid_seq),
                              srv.close_session(sid_seq)])[:n]
    blk_cfg = dataclasses.replace(cfg, block_frames="auto")
    assert np.array_equal(got_ll, stream_decode(blk_cfg, llr, n,
                                                chunk_frames=1))
    assert np.array_equal(got_seq, stream_decode(cfg, llr, n,
                                                 chunk_frames=1))
