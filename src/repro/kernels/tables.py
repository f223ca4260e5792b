"""In-kernel trellis table construction.

Pallas kernels may not capture array constants, so the (small) trellis
tables are rebuilt INSIDE the kernel from iota + static python ints
(k, polys) — the kernel body still sees loop-invariant vectors, exactly
like baking numpy tables would, but without captured-constant plumbing.
Only 2-D iota is used, since Mosaic lowers no 1-D iota and no
index-vector gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.trellis import Trellis

__all__ = ["butterfly_tables"]


def _parity(x: jax.Array, k: int) -> jax.Array:
    """Popcount-parity of k-bit ints (static unroll — k <= 16)."""
    out = jnp.zeros_like(x)
    for b in range(k):
        out = out ^ ((x >> b) & 1)
    return out


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
