"""The kernel tile of every launch the benchmark's chip cells make, pinned.

Each case is one launch shape of a cell in ``BENCHMARK.json``: the frame
geometry of its ``bench/configs/*.json`` and the frame count the kernel
sees (a 4-chip launch is sharded, so each chip's kernel sees a quarter).
The planner's answer for it (``autotune.launch_tile``, which is what
``ops.viterbi_decode_frames`` plans with on a TPU) is pinned as literals:
the frames a tile, its VMEM bytes under Mosaic padding, and the scoped
VMEM limit the kernel asks Mosaic for. These were the values before the
kernel's interpret-only paths were deleted, so a change here means the
chip no longer runs the programs it was measured with. The bucket plans
(``serve.bucket_plan``, no frame count) are pinned the same way. Plan
lookups only: nothing is decoded or compiled."""
import pytest

from repro.core import DecoderConfig, FrameSpec, make_trellis
from repro.kernels.autotune import launch_tile
from repro.serve import bucket_plan

#: (trellis recipe, frame geometry) per configuration, as its JSON gives them
CONFIGS = {
    "dvbs_r34": ((7, (0o133, 0o171)),
                 FrameSpec(f=288, v1=21, v2=45, f0=32, v2s=45)),
    "gsm_tchfs": ((5, (0o23, 0o33)),
                  FrameSpec(f=189, v1=20, v2=30, f0=27, v2s=30)),
    "umts_amr122": ((9, (0o557, 0o663, 0o711)),
                    FrameSpec(f=268, v1=36, v2=54, f0=67, v2s=54)),
}

MIB32 = 33554432

#: (cell, configuration, frames the kernel sees) -> (frames_per_tile,
#: vmem_bytes, vmem_limit)
LAUNCHES = {
    ("dvbs_r34.bulk8", "dvbs_r34", 1024): (128, 1458176, MIB32),
    ("umts_amr122.bulk8", "umts_amr122", 1024): (128, 3207168, MIB32),
    ("dvbs_r34.bulk32.4chip", "dvbs_r34", 4096 // 4): (128, 1458176, MIB32),
}
#: gsm_tchfs.calls launches 1-64 one-frame windows (slots 64)
GSM_TILES = {range(1, 9): 8, range(9, 17): 16, range(17, 33): 32,
             range(33, 65): 64}
LAUNCHES.update({("gsm_tchfs.calls", "gsm_tchfs", n): (ft, 843776, MIB32)
                 for frames, ft in GSM_TILES.items() for n in frames})

#: configuration -> (chunk_frames of its traffic, bucket frames_per_tile,
#: bucket vmem_bytes)
BUCKETS = {"dvbs_r34": (128, 128, 1458176),
           "gsm_tchfs": (1, 256, 1687552),
           "umts_amr122": (128, 128, 3207168)}


def test_every_cell_launch_is_pinned():
    assert len(LAUNCHES) == 67
    assert sorted(n for c, _, n in LAUNCHES if c == "gsm_tchfs.calls") == \
        list(range(1, 65))


@pytest.mark.parametrize("key", sorted(LAUNCHES),
                         ids=lambda k: f"{k[0]}-F{k[2]}")
def test_launch_tile_is_the_parents(key):
    _, name, frames = key
    code, spec = CONFIGS[name]
    tile = launch_tile(make_trellis(*code), spec, frames)
    assert (tile.frames_per_tile, tile.vmem_bytes, tile.vmem_limit) == \
        LAUNCHES[key]


@pytest.mark.parametrize("name", sorted(BUCKETS))
def test_bucket_plan_tile_is_the_parents(name):
    code, spec = CONFIGS[name]
    chunk, ft, vmem = BUCKETS[name]
    cfg = DecoderConfig(trellis=make_trellis(*code), spec=spec,
                        backend="kernel", interpret=False)
    plan = bucket_plan(cfg, chunk_frames=chunk)
    assert (plan.frames_per_tile, plan.tile.vmem_bytes) == (ft, vmem)
    assert (plan.pack_survivors, plan.radix, plan.chunk_frames) == \
        (True, 4, chunk)
