"""Bit-packing of survivor selectors (paper §IV-B, GPU idiom).

The ACS recursion produces ONE bit of information per (stage, state): the
selector that says which butterfly predecessor survived. Storing it in an
int32 wastes 32x the footprint. Every GPU Viterbi decoder in the
literature (Peng et al. arXiv:1608.00066; Mohammadidoost & Hashemi
arXiv:2011.13579) packs survivors into machine words; this module is the
TPU/Pallas equivalent.

The unified kernel keeps its states on the sublanes and its frames on the
128 lanes, so the packed-word axis sits on the sublanes too: the
selectors of one stage, (S, N) with N frames, pack into
``packed_width(S)`` rows of (1, N) int32, state ``s`` at bit ``s % 32`` of
word ``s // 32``:

    words[s // 32] >> (s % 32) & 1 == sel[s, :]

On Mosaic an (8 sublane x 128 lane) tile pads the trailing dim to 128, so
the tiny word axis belongs on the sublanes, where padding costs at most
8/W, with frames filling the lanes (kernels/autotune.py's
``mosaic_padded_bytes`` models exactly this). Codes with S < 32 states
(e.g. K=5) pack into one zero-padded word.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["BITS", "packed_width", "pack_rows"]

BITS = 32          # word width: int32 is the TPU-native integer lane type


def packed_width(n: int) -> int:
    """Number of int32 words needed for ``n`` selector bits (>= 1)."""
    return -(-n // BITS)


def pack_rows(sel: jnp.ndarray) -> list:
    """(n, N) {0,1}-valued -> ``packed_width(n)`` int32 rows of (1, N).

    The packing of one stage inside the compiled kernel: row ``s`` lands
    in word ``s // 32`` at bit ``s % 32``. Built from static 32-row slices
    and a 2-D iota shift — no reshape, which Mosaic cannot lower across
    the sublane axis. The rows are returned separately so the caller
    stores each at its own (unaligned) row offset. Bit 31 lands in the
    int32 sign bit; two's-complement wraparound keeps it exact."""
    x = sel.astype(jnp.int32)
    n = x.shape[0]
    words = []
    for w in range(packed_width(n)):
        blk = x[w * BITS:min(n, (w + 1) * BITS)]
        shift = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
        words.append(jnp.sum(blk << shift, axis=0, keepdims=True,
                             dtype=jnp.int32))
    return words
