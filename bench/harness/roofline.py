"""The chip's published peaks and the bytes the kernels must move, for
roofline shares computed from device time."""
from __future__ import annotations

import json

from .spec import BENCH


def peaks(device_kind: str, bench=BENCH) -> dict:
    """The published peaks of a device kind; a kind that is not in
    ``bench/peaks.json`` is an error, never a default."""
    table = json.loads((bench / "peaks.json").read_text())["devices"]
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.json; have "
                       f"{sorted(table)}") from None


def unified_kernel_bytes(frames: int, cfg: dict) -> int:
    """HBM bytes the unified Viterbi kernel moves for ``frames`` frames:
    the float32 LLR block in (L * beta values a frame) and the int32
    decoded bits out (f a frame). Survivors and path metrics stay in
    VMEM. The kernel's ACS work runs on the vector unit, for which no
    peak is published, so its only roofline is this memory one."""
    fr = cfg["frame"]
    L = fr["v1"] + fr["f"] + fr["v2"]
    beta = len(cfg["code"]["polys_octal"])
    return frames * (4 * L * beta + 4 * fr["f"])
