"""Unit tests for kernels/autotune.py — the VMEM-budget tile planner."""
import pytest

from repro.core import FrameSpec, STD_K7
from repro.core.trellis import make_trellis
from repro.kernels.autotune import (CANDIDATE_TILES, DEFAULT_VMEM_BUDGET,
                                    LANES, kernel_shapes,
                                    mosaic_padded_bytes, plan_decode,
                                    plan_tiles, unified_vmem_bytes,
                                    vmem_limit)

SPEC = FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45)
MIB16 = 16 * 1024 * 1024


def test_footprint_matches_kernel_scratch():
    """The model's sel term is the kernel's (L*S, lanes) int32 scratch of
    a 128-frame chunk under Mosaic padding, and packing shrinks exactly
    that term to (L*S/32, lanes) rows for S=64."""
    L, FT, S = SPEC.frame_len, 128, STD_K7.num_states
    _, plain = unified_vmem_bytes(STD_K7, SPEC, FT)
    _, packed = unified_vmem_bytes(STD_K7, SPEC, FT, pack_survivors=True)
    d_plain, d_packed = dict(plain), dict(packed)
    assert d_plain["sel_survivors"] == mosaic_padded_bytes((L * S, 128), 4)
    assert d_packed["sel_survivors"] == \
        mosaic_padded_bytes((L * (S // 32), 128), 4)
    assert d_plain["sel_survivors"] == L * S * 128 * 4
    # everything else is knob-independent
    for k in d_plain:
        if k != "sel_survivors":
            assert d_plain[k] == d_packed[k]
    # the model prices exactly the arrays the kernel allocates
    shapes = kernel_shapes(STD_K7, L, SPEC.f, FT, True)
    assert [n for n, _, _ in shapes] == [n for n, _ in packed]
    assert vmem_limit(shapes) == 32 << 20        # the limit's floor


def test_footprint_scales_linearly_in_ft():
    t128, _ = unified_vmem_bytes(STD_K7, SPEC, 128)
    t512, _ = unified_vmem_bytes(STD_K7, SPEC, 512)
    assert t512 == 4 * t128


def test_packed_plan_is_deeper():
    plain = plan_tiles(STD_K7, SPEC, vmem_budget=MIB16)
    packed = plan_tiles(STD_K7, SPEC, pack_survivors=True,
                        vmem_budget=MIB16)
    assert plain.frames_per_tile >= LANES
    assert packed.frames_per_tile >= 8 * LANES
    assert packed.frames_per_tile > plain.frames_per_tile
    assert packed.vmem_bytes <= packed.budget == MIB16
    # at the default budget the packed plan fills one 128-lane chunk
    p = plan_tiles(STD_K7, SPEC, pack_survivors=True)
    assert p.frames_per_tile == LANES
    assert p.vmem_bytes <= p.budget == DEFAULT_VMEM_BUDGET


def test_plan_respects_budget_and_floor():
    # a tiny budget still yields the smallest tile Mosaic can tile
    p = plan_tiles(STD_K7, SPEC, vmem_budget=1)
    assert p.frames_per_tile == LANES
    # a huge budget tops out at the largest candidate
    p = plan_tiles(STD_K7, SPEC, pack_survivors=True, vmem_budget=1 << 30)
    assert p.frames_per_tile == CANDIDATE_TILES[-1]
    assert 0 < p.utilization() < 1


def test_plan_caps_at_stream_length():
    p = plan_tiles(STD_K7, SPEC, pack_survivors=True, max_frames=5)
    assert p.frames_per_tile == 8                # one tile covers 5 frames


def test_plan_scales_with_state_count():
    """K=9 (S=256) frames are 4x heavier: the plan must shrink, not OOM."""
    k9 = make_trellis(9, (0o753, 0o561))
    p7 = plan_tiles(STD_K7, SPEC, pack_survivors=True, vmem_budget=MIB16)
    p9 = plan_tiles(k9, SPEC, pack_survivors=True, vmem_budget=MIB16)
    assert p9.frames_per_tile < p7.frames_per_tile
    assert p9.vmem_bytes <= p9.budget


def test_mosaic_padding_model():
    """The padded model is the (8,128)-tile arithmetic: trailing dim to
    128 lanes, second-to-last to 32/itemsize sublanes."""
    assert mosaic_padded_bytes((340, 32, 2), 4) == 340 * 32 * 128 * 4
    assert mosaic_padded_bytes((680, 128), 4) == 680 * 128 * 4  # no padding
    assert mosaic_padded_bytes((2, 128), 4) == 8 * 128 * 4      # sublane pad
    assert mosaic_padded_bytes((2, 128), 2) == 16 * 128 * 2     # bf16 tile
    assert mosaic_padded_bytes((2, 128), 1) == 32 * 128 * 1     # int8 tile
    assert mosaic_padded_bytes((64,), 4) == 8 * 128 * 4   # 1D: one full tile


def test_plan_decode_full_plan():
    """plan_decode returns everything the front-end executes: kernel
    kwargs splat into ops, and the chunk is a multiple of tiles x
    devices."""
    p = plan_decode(STD_K7, SPEC, num_devices=4)
    assert p.pack_survivors and p.radix == 4
    assert p.frames_per_tile == LANES
    assert p.chunk_frames == 2 * p.frames_per_tile * 4
    assert p.kernel_kwargs() == dict(
        frames_per_tile=p.frames_per_tile, pack_survivors=True, radix=4,
        block_frames=1, overlap=0)


def test_candidates_lift_the_256_cap():
    """The ROADMAP open item: candidates now grow from the budget up to
    the frame count. At 8 MiB the packed sublane plan exceeds the old 256
    cap; max_frames still picks the smallest covering candidate; the
    MAX_FRAMES_PER_TILE backstop bounds an unlimited budget."""
    from repro.kernels.autotune import MAX_FRAMES_PER_TILE
    assert CANDIDATE_TILES[-1] == MAX_FRAMES_PER_TILE > 256
    p = plan_tiles(STD_K7, SPEC, pack_survivors=True,
                   vmem_budget=8 * 1024 * 1024)
    assert p.frames_per_tile == 512 > 256
    assert p.vmem_bytes <= p.budget
    p2 = plan_tiles(STD_K7, SPEC, pack_survivors=True, vmem_budget=1 << 30,
                    max_frames=300)
    assert p2.frames_per_tile == 512          # smallest candidate >= 300


def test_kernel_runs_beyond_256_frames_per_tile():
    """A >256 frame tile actually decodes, bit-exact vs the reference
    (the plan space beyond the old cap is real, not just arithmetic)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.framed import FrameSpec, frame_llr
    from repro.kernels import ops, ref
    spec = FrameSpec(f=16, v1=8, v2=8)        # small L: 330 frames is cheap
    rng = np.random.default_rng(0)
    llr = jnp.asarray(rng.standard_normal((330 * 16, 2)).astype(np.float32))
    frames = frame_llr(llr, spec)
    want = np.asarray(ref.unified_decode_frames_ref(frames, STD_K7, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        frames, STD_K7, spec, frames_per_tile=512, pack_survivors=True,
        radix=4, interpret=True))
    assert np.array_equal(got, want)


def test_plan_cache_key_and_pinned_tile():
    """cache_key() is the serve layer's bucket identity: stable across
    equal plans, sensitive to every knob; frames_per_tile= pins the tile
    the session actually launches with (no autotuning surprise in the
    padding accounting)."""
    a = plan_decode(STD_K7, SPEC)
    b = plan_decode(STD_K7, SPEC)
    assert a.cache_key() == b.cache_key()
    assert a.fingerprint() == b.fingerprint()
    c = plan_decode(STD_K7, SPEC, radix=2)
    assert a.cache_key() != c.cache_key()
    d = plan_decode(STD_K7, SPEC, chunk_frames=7)
    assert a.cache_key() != d.cache_key()
    p = plan_decode(STD_K7, SPEC, frames_per_tile=8)
    assert p.frames_per_tile == 8
    assert p.chunk_frames == 2 * 8            # chunk follows the pinned tile


def test_geometry_validation_errors():
    """plan_tiles rejects broken subframe geometry with actionable errors
    (via FrameSpec.validate — one source of truth for the invariants)."""
    with pytest.raises(ValueError, match="multiple of f0"):
        plan_tiles(STD_K7, FrameSpec(f=256, v1=20, v2=45, f0=48, v2s=45))
    with pytest.raises(ValueError, match="exceeds v2"):
        plan_tiles(STD_K7, FrameSpec(f=256, v1=20, v2=20, f0=32, v2s=45))
    plan_tiles(STD_K7, SPEC)                     # sane spec passes


def test_plan_identity_differs_for_every_knob():
    """Property: cache_key()/fingerprint() are injective over the knobs —
    any single-knob change (including the block decomposition) yields a
    distinct identity, so the plan cache and serve buckets can never
    alias two plans that compile or decode differently."""
    import dataclasses
    base = plan_decode(STD_K7, SPEC)
    variants = [
        ("frames_per_tile",
         dataclasses.replace(base, tile=dataclasses.replace(
             base.tile, frames_per_tile=base.tile.frames_per_tile * 2))),
        ("pack_survivors", dataclasses.replace(base, pack_survivors=False)),
        ("radix", dataclasses.replace(base, radix=2)),
        ("chunk_frames",
         dataclasses.replace(base, chunk_frames=base.chunk_frames + 1)),
        ("num_devices", dataclasses.replace(base, num_devices=2)),
        ("block_frames", dataclasses.replace(base, block_frames=4,
                                             overlap=16)),
        ("overlap", dataclasses.replace(base, block_frames=4, overlap=20)),
    ]
    plans = [("base", base)] + variants
    keys = {}
    for name, plan in plans:
        key, fp = plan.cache_key(), plan.fingerprint()
        for other, (okey, ofp) in keys.items():
            assert key != okey, f"{name} aliases {other} in cache_key()"
            assert fp != ofp, f"{name} aliases {other} in fingerprint()"
        keys[name] = (key, fp)
    # footprint BOOKKEEPING is deliberately NOT identity: two plans that
    # picked the same knobs compile to the same kernel
    relabeled = dataclasses.replace(base, tile=dataclasses.replace(
        base.tile, vmem_bytes=base.tile.vmem_bytes + 1))
    assert relabeled.cache_key() == base.cache_key()
    assert relabeled.fingerprint() == base.fingerprint()


def test_fingerprint_stable_across_processes():
    """fingerprint() must be reproducible in a FRESH interpreter: the
    serve checkpoint stores it and a restored server recomputes it, so a
    hash seeded per-process (e.g. str hashing) would break every restore.
    Also pins the blocked-plan identity so a knob silently dropped from
    cache_key() fails loudly."""
    import subprocess
    import sys
    prog = (
        "from repro.core import FrameSpec, STD_K7\n"
        "from repro.kernels.autotune import plan_decode\n"
        "spec = FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45)\n"
        "p = plan_decode(STD_K7, spec, block_frames=4, overlap=45)\n"
        "print(p.fingerprint())\n")
    here = plan_decode(STD_K7, SPEC, block_frames=4, overlap=45)
    assert here.block_frames == 4 and here.overlap == 45
    out = subprocess.run([sys.executable, "-c", prog], check=True,
                         capture_output=True, text=True, env=None)
    assert out.stdout.strip() == here.fingerprint()
