"""Shared ACS scan bodies for the Pallas Viterbi kernels.

Both kernels (viterbi_unified, viterbi_fwd) run the identical forward
recursion — coalesced branch metrics, then the add-compare-select scan at
radix 2 or 4 — and differ only in where the survivor selectors go (VMEM
scratch vs HBM stream). The scans take a ``store`` callback, so a change
to the tie-break / normalization / radix-4 pair ordering cannot drift
between the two kernels and silently break their bit-exactness.

Layouts (kernels/packing.Layout):
  * SUBLANE — the compiled layout (``acs_scan_sublane``): frames on the
    lanes (in chunks of up to 128), states on sublanes. The ACS is the
    butterfly written with strided VMEM loads: the even and odd path
    metrics (every state's two predecessors) are read back from a
    (S, lanes) scratch with stride 2, so there is no gather anywhere.
    Branch metrics go to a flat (half * L, lanes) scratch, metric ``h``
    of stage ``t`` at row ``h * L + t``, de-interleaved from an
    (L * beta, lanes) LLR block with strided loads (no reshape). Every
    construct here is one Mosaic lowers.
  * LANE — the original orientation (``acs_scan_lane``), interpret mode
    only: working arrays are (FT, S), frames on sublanes, states on
    lanes; bm scratch is (L, FT, half). Its static-index ``jnp.take``
    gathers do not lower on Mosaic.

Both orientations perform the identical arithmetic sequence (exact ±1
sign flips, one add per candidate, the same select and max-normalize), so
they are bit-identical for float32 branch metrics.

``bm_dtype`` sets the *storage* dtype of the compressed branch metrics
(eq. 9): float32, or bfloat16 (interpret mode only) to halve the
second-largest VMEM term. Path metrics always accumulate in float32 — BMs
are rounded once on store and cast back up before the add, so bf16 costs
one quantization of the inputs, not a lossy recursion (tests/test_ber.py
bounds the BER delta).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.trellis import Trellis
from .tables import butterfly_tables, kernel_tables, radix4_tables

__all__ = ["acs_scan_lane", "acs_scan_sublane", "stage_loop"]


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


def acs_scan_sublane(llr_ref, bm_ref, sigma_ref, *, trellis: Trellis, L: int,
                     radix: int, store, bm_dtype=jnp.float32):
    """Branch metrics + butterfly ACS over all L stages, frames on lanes.

    Every ref is (C, rows, lanes): the frame tile as C lane-chunks of up
    to 128 frames (Mosaic's strided loads and stores need a 128-lane base
    array). Each stage runs the C chunks side by side.

    llr_ref:   (C, L * beta, lanes) LLR block; coded bit b of stage t at
               row ``t * beta + b``.
    bm_ref:    (C, half * L, lanes) VMEM scratch for the symmetry-
               compressed branch metrics (paper Fig. 7 / eq. 9), metric h
               of stage t at row ``h * L + t``; dtype ``bm_dtype``.
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
            bm_ref[c, pl.ds(h * L, L), :] = acc.astype(bm_dtype)

    # loop-invariant lookup masks per (half, predecessor)
    tabs = [[([idx == h for h in range(1, half)], neg)
             for idx, neg in row]
            for row in butterfly_tables(trellis, (S2, lanes))]

    def chunk_stage(t, c):
        prev = (sigma_ref[c, pl.ds(0, S2, stride=2), :],     # 2r
                sigma_ref[c, pl.ds(1, S2, stride=2), :])     # 2r + 1
        rows = [bm_ref[c, pl.ds(h * L + t, 1), :].astype(jnp.float32)
                for h in range(half)]
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


def acs_scan_lane(llr_ref, bm_ref, *, trellis: Trellis, L: int, radix: int,
                  store, bm_dtype=jnp.float32):
    """Branch metrics + ACS over all L stages, frames on sublanes
    (interpret mode only); returns the final sigma.

    llr_ref: (FT, L, beta) kernel input ref.
    bm_ref:  (L, FT, half) VMEM scratch, dtype ``bm_dtype``.
    store:   callback invoked once per stage, in stage order, with
             (t, sel, sigma), both (FT, S).

    radix=4 fuses two stages per scan step via the fused BM indexing of
    ``radix4_tables`` — half the trip count, bit-identical arithmetic
    (each half-step is the exact radix-2 sequence incl. normalization).
    """
    S = trellis.num_states
    FT = llr_ref.shape[0]
    if radix == 4:
        perm, idx2, sgn2, signs_half = radix4_tables(trellis)
    else:
        perm, idx_p, sgn_p, signs_half = kernel_tables(trellis)
        idx2, sgn2 = [idx_p], [sgn_p]

    # coalesced, symmetry-compressed branch metrics into VMEM
    llr = llr_ref[...].astype(jnp.float32)
    bm_ref[...] = jnp.einsum("flb,hb->lfh", llr, signs_half).astype(bm_dtype)

    def bmrow(t, k=1):
        if k == 1:
            return bm_ref[t]
        return jnp.concatenate([bm_ref[t], bm_ref[t + 1]], axis=1)

    def acs_half(sigma, bmr, st):                    # one radix-2 half-step
        cand = []
        for p in (0, 1):
            s_prev = jnp.take(sigma, perm[p], axis=1)              # (FT, S)
            bm = (jnp.take(bmr, idx2[st][p], axis=1)
                  .astype(jnp.float32) * sgn2[st][p])
            cand.append(s_prev + bm)
        sel = (cand[1] >= cand[0])                   # ties -> i'' (Alg. 1)
        sigma = jnp.where(sel, cand[1], cand[0])
        sigma = sigma - jnp.max(sigma, axis=1, keepdims=True)   # normalize
        return sigma, sel

    sigma0 = jnp.zeros((FT, S), jnp.float32)
    if radix == 4:
        def acs_pair(t2, sigma):
            t = 2 * t2
            bm2 = bmrow(t, 2)             # both stages' rows, fused indexing
            for st in (0, 1):                        # exact radix-2 order
                sigma, sel = acs_half(sigma, bm2, st)
                store(t + st, sel, sigma)
            return sigma
        sigma = jax.lax.fori_loop(0, L // 2, acs_pair, sigma0)
        if L % 2:                                    # odd-length tail stage
            sigma, sel = acs_half(sigma, bmrow(L - 1), 0)
            store(L - 1, sel, sigma)
        return sigma

    def acs_step(t, sigma):
        sigma, sel = acs_half(sigma, bmrow(t), 0)
        store(t, sel, sigma)
        return sigma
    return jax.lax.fori_loop(0, L, acs_step, sigma0)
