"""Lane-wise bit-packing of survivor selectors (paper §IV-B, GPU idiom).

The ACS recursion produces ONE bit of information per (stage, state): the
selector that says which butterfly predecessor survived. The seed kernels
stored that bit in an int32 (unified kernel VMEM scratch) or an int8 (split
kernel's HBM stream), wasting 32x / 8x the footprint. Every GPU Viterbi
decoder in the literature (Peng et al. arXiv:1608.00066; Mohammadidoost &
Hashemi arXiv:2011.13579) packs survivors into machine words; this module
is the TPU/Pallas equivalent.

Two physical layouts, selected by the ``Layout`` enum:

``Layout.LANE`` (the PR-1 layout) packs along the trailing (state = lane)
axis, contiguous — word ``w`` of a packed row holds states ``[32w, 32w+32)``
with state ``s`` at bit ``s % 32``:

    packed[..., s // 32] >> (s % 32) & 1 == sel[..., s]

``Layout.SUBLANE`` is the Mosaic-native variant: the packed-word axis sits
at position -2 (the TPU *sublane* dimension) and the payload axis — frames
in the kernels — stays trailing, on the 128 *lanes*:

    sel (..., S, N)  ->  packed (..., W, N),
    packed[..., s // 32, :] >> (s % 32) & 1 == sel[..., s, :]

On real Mosaic an (8 sublane x 128 lane) tile pads the trailing dim to 128,
so a lane-packed ``(.., W=2)`` array is allocated as if it were 128 wide —
the 32x compression evaporates. Sublane packing puts the tiny W dim where
padding costs at most 8/W and fills the lanes with frames, which is what
makes the compression survive compiled mode (kernels/autotune.py's
``mosaic_padded_bytes`` models exactly this).

All functions are pure jnp on static shapes. ``pack_rows`` is the form
the compiled kernel packs with (no reshape); the others serve interpret-
mode kernel bodies and the JAX level (packing the split kernel's HBM
stream, the JAX tracebacks).
Codes with S < 32 states (e.g. K=5, K=4 test codes) pack into one
zero-padded word — still a win vs S int8s for S > 4.
"""
from __future__ import annotations

import enum

import jax
import jax.numpy as jnp

__all__ = ["BITS", "Layout", "packed_width", "pack_bits", "pack_rows",
           "unpack_bits", "extract_bit"]

BITS = 32          # word width: int32 is the TPU-native integer lane type


class Layout(str, enum.Enum):
    """Physical placement of the packed-word axis (TPU tiling aware)."""
    LANE = "lane"         # words trailing (lanes): (..., N, W) from (..., N, S)
    SUBLANE = "sublane"   # words at -2 (sublanes): (..., W, N) from (..., S, N)


def packed_width(n: int) -> int:
    """Number of int32 words needed for ``n`` selector bits (>= 1)."""
    return -(-n // BITS)


def _pack_last(sel: jnp.ndarray) -> jnp.ndarray:
    """(..., n) {0,1}-valued -> (..., packed_width(n)) int32 along -1."""
    n = sel.shape[-1]
    w = packed_width(n)
    x = sel.astype(jnp.int32)
    if w * BITS != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, w * BITS - n)]
        x = jnp.pad(x, pad)
    x = x.reshape(*x.shape[:-1], w, BITS)
    weights = jnp.left_shift(jnp.int32(1),
                             jnp.arange(BITS, dtype=jnp.int32))
    return jnp.sum(x * weights, axis=-1, dtype=jnp.int32)


def pack_bits(sel: jnp.ndarray, layout: Layout = Layout.LANE) -> jnp.ndarray:
    """Pack selector bits into int32 words.

    LANE:    pack axis -1;  (..., n)    -> (..., w).
    SUBLANE: pack axis -2;  (..., n, N) -> (..., w, N) — the bit axis is the
             second-to-last (sublane) dim, the trailing payload axis (frames
             on lanes) is untouched.

    Bit ``n % 32 == 31`` lands in the int32 sign bit; two's-complement
    wraparound in the weighted sum makes that exact.
    """
    if Layout(layout) is Layout.LANE:
        return _pack_last(sel)
    n = sel.shape[-2]
    w = packed_width(n)
    x = sel.astype(jnp.int32)
    if w * BITS != n:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, w * BITS - n), (0, 0)]
        x = jnp.pad(x, pad)
    x = x.reshape(*x.shape[:-2], w, BITS, x.shape[-1])
    weights = jnp.left_shift(jnp.int32(1),
                             jnp.arange(BITS, dtype=jnp.int32))[:, None]
    return jnp.sum(x * weights, axis=-2, dtype=jnp.int32)


def pack_rows(sel: jnp.ndarray) -> list:
    """(n, N) {0,1}-valued -> ``packed_width(n)`` int32 rows of (1, N).

    The SUBLANE packing of one stage inside a compiled kernel: row ``s``
    lands in word ``s // 32`` at bit ``s % 32``. Built from static
    32-row slices and a 2-D iota shift — no reshape, which Mosaic cannot
    lower across the sublane axis. The rows are returned separately so
    the caller stores each at its own (unaligned) row offset."""
    x = sel.astype(jnp.int32)
    n = x.shape[0]
    words = []
    for w in range(packed_width(n)):
        blk = x[w * BITS:min(n, (w + 1) * BITS)]
        shift = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
        words.append(jnp.sum(blk << shift, axis=0, keepdims=True,
                             dtype=jnp.int32))
    return words


def unpack_bits(packed: jnp.ndarray, n: int,
                layout: Layout = Layout.LANE) -> jnp.ndarray:
    """Inverse of pack_bits for either layout (values in {0, 1})."""
    shifts = jnp.arange(BITS, dtype=jnp.int32)
    if Layout(layout) is Layout.LANE:
        w = packed.shape[-1]
        bits = (packed[..., :, None] >> shifts) & 1      # (..., w, 32)
        return bits.reshape(*packed.shape[:-1], w * BITS)[..., :n]
    w = packed.shape[-2]
    bits = (packed[..., :, None, :] >> shifts[:, None]) & 1  # (..., w, 32, N)
    out = bits.reshape(*packed.shape[:-2], w * BITS, packed.shape[-1])
    return out[..., :n, :]


def extract_bit(packed_row: jnp.ndarray, state: jnp.ndarray,
                layout: Layout = Layout.LANE) -> jnp.ndarray:
    """Selector bit of ``state`` from a packed row.

    LANE:    packed_row (..., w) int32, state (...) broadcast-compatible
             with the leading dims.
    SUBLANE: packed_row (..., w, N) int32, state (..., N) — one lookup per
             trailing lane, words gathered across the sublane axis.

    Uses a word-index one-hot reduction instead of a data-dependent gather
    so it lowers to pure vector ops inside Pallas kernels (mirrors the
    unpacked kernels' one-hot selector extraction). The ``& 1`` after the
    arithmetic shift makes sign-extension of bit-31 words harmless.
    """
    if Layout(layout) is Layout.LANE:
        w = packed_row.shape[-1]
        word_id = state >> 5                             # state // 32
        lanes = jnp.arange(w, dtype=jnp.int32)
        onehot = (word_id[..., None] == lanes).astype(jnp.int32)
        word = jnp.sum(packed_row * onehot, axis=-1)
        return (word >> (state & (BITS - 1))) & 1
    w = packed_row.shape[-2]
    word_id = state >> 5                                 # (..., N)
    subs = jnp.arange(w, dtype=jnp.int32)[:, None]       # (w, 1)
    onehot = (word_id[..., None, :] == subs).astype(jnp.int32)  # (..., w, N)
    word = jnp.sum(packed_row * onehot, axis=-2)
    return (word >> (state & (BITS - 1))) & 1
