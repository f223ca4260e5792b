"""The chip's kernel against the reference on the UMTS voice channel (3GPP TS 25.212 4.2.3.1: K=9, rate 1/3, G0=557, G1=663, G2=711).

The unified kernel, interpreted on the CPU (the program Mosaic compiles on
a TPU), decodes 16 noisy frames of the benchmark cell's frame geometry
(``bench/configs/umts_amr122.json``) bit-identically to
``kernels.ref.unified_decode_frames_ref``, for every radix, survivor
packing and traceback."""
import pytest

from repro.core import FrameSpec, make_trellis

from conftest import kernel_matches_reference

TRELLIS = make_trellis(9, (0o557, 0o663, 0o711))
CELL = FrameSpec(f=268, v1=36, v2=54, f0=67, v2s=54)
TRACEBACKS = {"parallel": CELL,
              "serial": FrameSpec(f=CELL.f, v1=CELL.v1, v2=CELL.v2)}


@pytest.mark.parametrize("traceback", sorted(TRACEBACKS))
@pytest.mark.parametrize("pack", [True, False], ids=["packed", "unpacked"])
@pytest.mark.parametrize("radix", [2, 4])
def test_kernel_matches_reference(radix, pack, traceback):
    kernel_matches_reference(TRELLIS, TRACEBACKS[traceback], radix, pack,
                             seed=radix * 10 + pack)
