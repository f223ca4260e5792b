"""The ACS scan body of the unified Pallas Viterbi kernel.

Frames sit on the lanes (in chunks of up to 128), states on the
sublanes. The ACS is the butterfly written with strided VMEM loads: the
even and odd path metrics (every state's two predecessors) are read back
from a (S, lanes) scratch with stride 2, so there is no gather anywhere.
Branch metrics go to a flat (half * L, lanes) float32 scratch, metric
``h`` of stage ``t`` at row ``h * L + t``, de-interleaved from an
(L * beta, lanes) LLR block with strided loads (no reshape). Every
construct here is one Mosaic lowers. Radix 4 runs two stages per scan
step with the exact radix-2 arithmetic (``stage_loop``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.trellis import Trellis
from .tables import butterfly_tables

__all__ = ["acs_scan", "stage_loop"]


def stage_loop(lo: int, hi: int, body, carry, radix: int):
    """``fori_loop(lo, hi, body, carry)``; radix 4 runs two stages per
    trip (half the trip count, same per-stage sequence, odd tail last)."""
    if radix != 4:
        return jax.lax.fori_loop(lo, hi, body, carry)
    n = hi - lo
    carry = jax.lax.fori_loop(
        0, n // 2, lambda i, c: body(lo + 2 * i + 1, body(lo + 2 * i, c)),
        carry)
    return body(hi - 1, carry) if n % 2 else carry


def acs_scan(llr_ref, bm_ref, sigma_ref, *, trellis: Trellis, L: int,
                     radix: int, store):
    """Branch metrics + butterfly ACS over all L stages, frames on lanes.

    Every ref is (C, rows, lanes): the frame tile as C lane-chunks of up
    to 128 frames (Mosaic's strided loads and stores need a 128-lane base
    array). Each stage runs the C chunks side by side.

    llr_ref:   (C, L * beta, lanes) LLR block; coded bit b of stage t at
               row ``t * beta + b``.
    bm_ref:    (C, half * L, lanes) float32 VMEM scratch for the
               symmetry-compressed branch metrics (paper Fig. 7 / eq. 9),
               metric h of stage t at row ``h * L + t``.
    sigma_ref: (C, S, lanes) float32 VMEM scratch carrying the path
               metrics from stage to stage; when ``store(t, c, ...)`` runs
               it holds chunk c's normalized stage-t metrics.
    store:     callback run once per stage and chunk, in stage order, with
               ``(t, c, (sel_top, sel_bot))`` — the (S/2, lanes) bool
               selectors of states [0, S/2) and [S/2, S).
    """
    S = trellis.num_states
    S2 = S // 2
    beta = trellis.beta
    half = 1 << (beta - 1)
    C, _, lanes = sigma_ref.shape

    # coalesced, symmetry-compressed branch metrics into VMEM: metric h is
    # sum_b ±llr_b with sign bit b of output word h (exact sign flips)
    for c in range(C):
        llr = [llr_ref[c, pl.ds(b, L, stride=beta), :].astype(jnp.float32)
               for b in range(beta)]
        for h in range(half):
            acc = None
            for b in range(beta):
                term = -llr[b] if (h >> (beta - 1 - b)) & 1 else llr[b]
                acc = term if acc is None else acc + term
            bm_ref[c, pl.ds(h * L, L), :] = acc

    # loop-invariant lookup masks per (half, predecessor)
    tabs = [[([idx == h for h in range(1, half)], neg)
             for idx, neg in row]
            for row in butterfly_tables(trellis, (S2, lanes))]

    def chunk_stage(t, c):
        prev = (sigma_ref[c, pl.ds(0, S2, stride=2), :],     # 2r
                sigma_ref[c, pl.ds(1, S2, stride=2), :])     # 2r + 1
        rows = [bm_ref[c, pl.ds(h * L + t, 1), :] for h in range(half)]
        new, sel = [], []
        for hh in (0, 1):
            cand = []
            for p in (0, 1):
                is_h, neg = tabs[hh][p]
                bm = rows[0]
                for h in range(1, half):
                    bm = jnp.where(is_h[h - 1], rows[h], bm)
                cand.append(prev[p] + jnp.where(neg, -bm, bm))
            s = cand[1] >= cand[0]                   # ties -> i'' (Alg. 1)
            new.append(jnp.where(s, cand[1], cand[0]))
            sel.append(s)
        top = jnp.max(new[0], axis=0, keepdims=True)
        m = jnp.maximum(top, jnp.max(new[1], axis=0, keepdims=True))
        sigma_ref[c, pl.ds(0, S2), :] = new[0] - m   # normalize
        sigma_ref[c, pl.ds(S2, S2), :] = new[1] - m
        store(t, c, sel)

    def stage(t, carry):
        for c in range(C):
            chunk_stage(t, c)
        return carry

    sigma_ref[...] = jnp.zeros(sigma_ref.shape, jnp.float32)
    stage_loop(0, L, stage, 0, radix)

