"""In-kernel trellis table construction.

Pallas kernels may not capture array constants, so the (small) trellis
tables are rebuilt INSIDE the kernel from iota + static python ints
(k, polys) — the kernel body still sees loop-invariant vectors, exactly
like baking numpy tables would, but without captured-constant plumbing.

``butterfly_tables`` is the form the compiled (sublane) kernel uses: 2-D
iota only, since Mosaic lowers no 1-D iota and no index-vector gather.
``kernel_tables``/``radix4_tables`` serve the interpret-only lane layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.trellis import Trellis

__all__ = ["kernel_tables", "radix4_tables", "butterfly_tables"]


def _parity(x: jax.Array, k: int) -> jax.Array:
    """Popcount-parity of k-bit ints (static unroll — k <= 16)."""
    out = jnp.zeros_like(x)
    for b in range(k):
        out = out ^ ((x >> b) & 1)
    return out


def kernel_tables(trellis: Trellis):
    """Build {prev (S,2), bm_idx_p, bm_sgn_p [(S,) x2], signs_half} via iota."""
    k, beta, polys = trellis.k, trellis.beta, trellis.polys
    S = 1 << (k - 1)
    half = 1 << (beta - 1)
    mask = (1 << beta) - 1
    j = jax.lax.iota(jnp.int32, S)
    binput = j >> (k - 2)                           # input bit INTO state j

    prev, idx_p, sgn_p = [], [], []
    for p in (0, 1):
        prev_p = ((j << 1) & (S - 1)) | p           # butterfly predecessor
        w = (binput << (k - 1)) | prev_p            # k-bit encoder word
        oword = jnp.zeros_like(j)
        for bi, g in enumerate(polys):
            oword = oword | (_parity(w & g, k) << (beta - 1 - bi))
        # symmetry compression (eqs. 8-9): index into 2^(beta-1) table + sign
        idx = jnp.where(oword < half, oword, mask ^ oword)
        sgn = jnp.where(oword < half, 1.0, -1.0).astype(jnp.float32)
        prev.append(prev_p)
        idx_p.append(idx)
        sgn_p.append(sgn)

    o = jax.lax.iota(jnp.int32, half)[:, None]      # (half, 1)
    bi = jax.lax.iota(jnp.int32, beta)[None, :]     # (1, beta)
    bits = (o >> (beta - 1 - bi)) & 1
    signs_half = (1.0 - 2.0 * bits).astype(jnp.float32)   # (half, beta)
    return prev, idx_p, sgn_p, signs_half


def radix4_tables(trellis: Trellis):
    """Tables for the fused two-stage (radix-4) ACS pair step.

    The convolutional trellis is time-invariant, so both half-steps of a
    radix-4 pair share the butterfly predecessor permutation ``perm``.
    What the pair step DOES precompute is the fused branch-metric lookup:
    the kernel stores the two stages' compressed BM rows side by side as
    one ``(FT, 2 * half)`` vector, and ``idx2[st][p] = idx_p[p] + st*half``
    indexes straight into it — four BM gathers per pair against one fused
    table instead of two gathers against each of two rows.

    Exactness: ``take(bm2, idx2[st][p]) == take(bm_stage_st, idx_p[p])``
    element-for-element, and the pair step runs the two half-steps in the
    exact radix-2 arithmetic order (including the per-stage max-normalize),
    so radix-4 is bit-identical to radix-2 by construction — the win is a
    2x shorter scan (half the loop-control / scalar overhead per stage),
    not different arithmetic.
    """
    half = 1 << (trellis.beta - 1)
    prev, idx_p, sgn_p, signs_half = kernel_tables(trellis)
    idx2 = [[idx_p[p] + st * half for p in (0, 1)] for st in (0, 1)]
    sgn2 = [[sgn_p[p] for p in (0, 1)] for st in (0, 1)]
    return prev, idx2, sgn2, signs_half


def butterfly_tables(trellis: Trellis, shape: tuple):
    """Branch-metric lookup of the butterfly ACS, as ``shape`` arrays.

    The states split into halves ``hh`` (input bit ``hh`` into the state):
    row ``r`` of half ``hh`` is state ``j = hh * S/2 + r``, and both
    predecessors of ``j`` are ``2r + p`` — the even (p=0) and odd (p=1)
    path metrics, whatever the half. ``out[hh][p] = (idx, neg)``: the
    transition's metric is ``bm_half[idx]``, negated where ``neg`` (the
    eq.-8/9 symmetry compression). Built from a 2-D iota along axis 0
    (``shape[0] == S/2``), so every table is a plain vector of the
    kernel's working shape."""
    k, beta, polys = trellis.k, trellis.beta, trellis.polys
    half = 1 << (beta - 1)
    mask = (1 << beta) - 1
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    out = []
    for hh in (0, 1):
        row = []
        for p in (0, 1):
            w = (hh << (k - 1)) | (r << 1) | p       # k-bit encoder word
            oword = jnp.zeros_like(r)
            for bi, g in enumerate(polys):
                oword = oword | (_parity(w & g, k) << (beta - 1 - bi))
            row.append((jnp.where(oword < half, oword, mask ^ oword),
                        oword >= half))
        out.append(row)
    return out
