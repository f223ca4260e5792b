"""LLR input hardening: NaN/Inf scrub and out-of-range clamp.

A decode service fed by real demodulators sees poisoned buffers: NaN/Inf
from upstream DSP bugs, and absurd magnitudes from AGC glitches. A single
NaN is not locally contained — it propagates through the ACS max into
every path metric of its frame (NaN poisons ``max`` comparisons), turning
one bad sample into a garbage frame; ±Inf saturates the metrics and
``inf - inf = NaN`` in the per-stage normalization does the same. The fix
is cheap and information-theoretically sound: a non-finite soft symbol
carries no information, so it becomes the neutral zero LLR — exactly how
depuncturing treats erased symbols (paper §IV-E) — and finite outliers
clamp to ``±clip``, preserving their sign (the hard decision) while
bounding the metric growth fp32/bf16 must absorb.

``sanitize_llr`` is the host-side boundary filter used by the stream and
serve push paths; ``make_decoder`` applies the same rule in-graph. Both
are BIT-IDENTICAL on clean inputs: values that are finite and within
``±clip`` pass through untouched (the host path returns the input array
itself when nothing needs fixing, and learns that from one max and one
min, with no temporary; only a push that fails that check pays for the
full scan).
"""
from __future__ import annotations

import numpy as np

__all__ = ["LLR_CLIP", "sanitize_llr"]

#: Default magnitude clamp. Far beyond any sane LLR (|llr| ~ tens at the
#: SNRs where decoding is meaningful) yet small enough that a whole decode
#: window of clamped symbols stays orders of magnitude inside fp32 range
#: even with per-stage renormalization disabled.
LLR_CLIP = 1e6


def sanitize_llr(llr, clip: float = LLR_CLIP,
                 policy: str = "zero") -> tuple[np.ndarray, int]:
    """Scrub an LLR buffer; returns ``(clean, n_bad)``.

    policy='zero'  : NaN/Inf -> 0.0 (neutral erasure), |x| > clip ->
                     ±clip. Returns the INPUT array untouched when
                     n_bad == 0 — the clean path is bit-identical and
                     copy-free.
    policy='raise' : raise ValueError on the first poisoned buffer
                     (strict tenants who prefer rejection to erasure).
    policy='off'   : no scan at all; returns (asarray(llr), 0).
    """
    arr = np.asarray(llr, np.float32)
    if policy == "off":
        return arr, 0
    if policy not in ("zero", "raise"):
        raise ValueError(f"sanitize policy must be 'zero', 'raise' or "
                         f"'off', got {policy!r}")
    # Fast check: a clean push has nothing to fix exactly when every value
    # lies in [-c, c]. NaN carries through max/min and fails the test, as
    # does ±Inf. ``c`` is the largest finite float32 not above ``clip``, so
    # the check passes nothing the scan below flags, whether the scan's
    # comparison runs in float32 (a Python float clip) or in float64 (a
    # numpy float64 clip). Whatever fails the check goes to the exact scan.
    c = np.float32(clip)
    if c == np.inf or float(c) > clip:
        c = np.nextafter(c, np.float32(-np.inf))
    if arr.size == 0 or (arr.max() <= c and arr.min() >= -c):
        return arr, 0
    finite = np.isfinite(arr)
    bad = ~finite | (np.abs(arr) > clip)
    n_bad = int(bad.sum())
    if n_bad == 0:
        return arr, 0
    if policy == "raise":
        raise ValueError(
            f"{n_bad} non-finite or out-of-range (|llr| > {clip:g}) "
            f"values in a push of {arr.size}")
    out = np.where(finite, np.clip(arr, -clip, clip), np.float32(0.0))
    return out.astype(np.float32, copy=False), n_bad
