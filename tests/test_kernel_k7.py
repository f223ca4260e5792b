"""The chip's kernel against the reference on the DVB-S inner code (ETSI EN 300 421 4.4.3: K=7, G1=171, G2=133).

The unified kernel, interpreted on the CPU (the program Mosaic compiles on
a TPU), decodes 16 noisy frames of the benchmark cell's frame geometry
(``bench/configs/dvbs_r34.json``) bit-identically to
``kernels.ref.unified_decode_frames_ref``, for every radix, survivor
packing and traceback."""
import pytest

from repro.core import FrameSpec, make_trellis

from conftest import kernel_matches_reference

TRELLIS = make_trellis(7, (0o133, 0o171))
CELL = FrameSpec(f=288, v1=21, v2=45, f0=32, v2s=45)
TRACEBACKS = {"parallel": CELL,
              "serial": FrameSpec(f=CELL.f, v1=CELL.v1, v2=CELL.v2)}


@pytest.mark.parametrize("traceback", sorted(TRACEBACKS))
@pytest.mark.parametrize("pack", [True, False], ids=["packed", "unpacked"])
@pytest.mark.parametrize("radix", [2, 4])
def test_kernel_matches_reference(radix, pack, traceback):
    kernel_matches_reference(TRELLIS, TRACEBACKS[traceback], radix, pack,
                             seed=radix * 10 + pack)
