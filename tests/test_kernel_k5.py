"""The chip's kernel against the reference on the GSM full-rate speech channel (3GPP TS 45.003 3.1: K=5, rate 1/2, G0=23, G1=33).

The unified kernel, interpreted on the CPU (the program Mosaic compiles on
a TPU), decodes 16 noisy frames of the benchmark cell's frame geometry
(``bench/configs/gsm_tchfs.json``) bit-identically to
``kernels.ref.unified_decode_frames_ref``, for every radix, survivor
packing and traceback."""
import pytest

from repro.core import FrameSpec, make_trellis

from conftest import kernel_matches_reference

TRELLIS = make_trellis(5, (0o23, 0o33))
CELL = FrameSpec(f=189, v1=20, v2=30, f0=27, v2s=30)
TRACEBACKS = {"parallel": CELL,
              "serial": FrameSpec(f=CELL.f, v1=CELL.v1, v2=CELL.v2)}


@pytest.mark.parametrize("traceback", sorted(TRACEBACKS))
@pytest.mark.parametrize("pack", [True, False], ids=["packed", "unpacked"])
@pytest.mark.parametrize("radix", [2, 4])
def test_kernel_matches_reference(radix, pack, traceback):
    kernel_matches_reference(TRELLIS, TRACEBACKS[traceback], radix, pack,
                             seed=radix * 10 + pack)
