"""Branch metrics (paper §II-B eq. 2 and §IV-B optimizations).

delta_t(o) = sum_b (-1)^{o[b]} * llr_t[b]   for an output word o (beta bits).

Per stage there are only 2^beta distinct metrics ("repetitive patterns"),
and for standard codes delta(~o) = -delta(o) (eq. 8), so only 2^(beta-1)
values need to be computed/stored (eq. 9) — half the shared-memory (VMEM)
footprint.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .trellis import Trellis

__all__ = ["branch_metrics_full", "branch_metrics_half", "expand_half"]


def _signed_sums(llr: jax.Array, signs) -> jax.Array:
    """(n, beta) llr x (m, beta) ±1 table -> (n, m): sum_b signs[o, b] *
    llr[:, b], added left to right in float32. The products are exact, so
    every platform computes the same bits — a matmul would not: on a TPU
    its default precision rounds the inputs to bfloat16."""
    llr = llr.astype(jnp.float32)
    out = llr[..., None, 0] * signs[:, 0]
    for b in range(1, signs.shape[1]):
        out = out + llr[..., None, b] * signs[:, b]
    return out


def branch_metrics_full(llr: jax.Array, trellis: Trellis) -> jax.Array:
    """(n, beta) llr -> (n, 2^beta) metrics for every output word (eq. 7)."""
    return _signed_sums(llr, trellis.out_signs)       # (n, 2^beta)


def branch_metrics_half(llr: jax.Array, trellis: Trellis) -> jax.Array:
    """(n, beta) llr -> (n, 2^(beta-1)) compressed metrics (eqs. 8-9)."""
    half = 1 << (trellis.beta - 1)
    return _signed_sums(llr, trellis.out_signs[:half])


def expand_half(bm_half: jax.Array, trellis: Trellis) -> jax.Array:
    """Reconstruct the full (.., 2^beta) table from the compressed half."""
    idx = jnp.asarray(trellis.bm_index)               # (2^beta,)
    sgn = jnp.asarray(trellis.bm_sign).astype(bm_half.dtype)
    return bm_half[..., idx] * sgn
