"""Reference full-sequence Viterbi decoder (paper Alg. 1 + Alg. 2).

This is the exact, serial-traceback algorithm: the baseline row (a) of the
paper's Table I. It is the BER gold standard every framed/parallel variant is
validated against, and the oracle for the Pallas kernels.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .metrics import branch_metrics_half, expand_half
from .trellis import Trellis

__all__ = ["viterbi_forward", "viterbi_traceback", "viterbi_decode"]

NEG = np.float32(-1e30)    # "minus infinity" for unreachable-ish inits


def viterbi_forward(llr: jax.Array, trellis: Trellis,
                    sigma0: jax.Array | None = None, radix: int = 2,
                    renorm_every: int = 1):
    """Alg. 1: ACS over all stages.

    Args:
      llr: (n, beta) soft inputs (zero entries are neutral / depunctured).
      sigma0: optional (S,) initial path metrics (zeros = unknown start, as
        in framed decoding; the full decoder biases state 0).
      radix: 2 = one trellis stage per scan step; 4 = two stages fused per
        scan step (half the trip count — mirrors the kernels' radix-4 ACS).
        Each fused half-step performs the identical arithmetic sequence
        (candidates, select, max-normalize), so outputs are bit-identical.
      renorm_every: path-metric renormalization period — subtract the
        stage max every N stages. 1 (default) is the historical per-stage
        normalization (DESIGN §8, also what the Pallas kernels do); 0
        disables it entirely (metrics grow ~|llr|·n — safe only for
        bounded n and sane inputs, the baseline the renormalized path is
        gated bit-identical against on clean streams); N>1 amortizes the
        max reduction over N stages. Only the radix-2 path supports
        N != 1 (the reference backend's path).

    Returns:
      sel:   (n, S) int8 selector bits (0 -> predecessor 2j, 1 -> 2j+1);
             this *is* pi, stored compressed (1 bit of info per cell).
      sigma: (S,) final path metrics (max-normalized per stage).
      amax:  (n,) int32 argmax state per stage (for parallel-traceback
             boundary starts, paper §IV-D second solution).
    """
    S = trellis.num_states
    prev_state = jnp.asarray(trellis.prev_state)      # (S, 2)
    prev_out = jnp.asarray(trellis.prev_out)          # (S, 2)
    bm_half = branch_metrics_half(llr, trellis)       # (n, 2^(beta-1))
    if sigma0 is None:
        sigma0 = jnp.zeros((S,), jnp.float32)
    assert radix in (2, 4), radix
    assert renorm_every >= 0, renorm_every

    if renorm_every != 1:
        # periodic (or disabled) renormalization: the per-stage norm mask
        # rides along the scan. Kept separate from the default path below
        # so renorm_every=1 keeps its exact historical graph.
        assert radix == 2, "renorm_every != 1 requires radix=2 (reference)"
        n = bm_half.shape[0]
        if renorm_every > 0:
            norm_mask = (jnp.arange(n) % renorm_every) == (renorm_every - 1)
        else:
            norm_mask = jnp.zeros((n,), bool)

        def step_renorm(sigma, xs):
            bmh, do_norm = xs
            bm = expand_half(bmh, trellis)
            cand0 = sigma[prev_state[:, 0]] + bm[prev_out[:, 0]]
            cand1 = sigma[prev_state[:, 1]] + bm[prev_out[:, 1]]
            sel = (cand1 >= cand0)
            new = jnp.where(sel, cand1, cand0)
            new = jnp.where(do_norm, new - jnp.max(new), new)
            return new, (sel.astype(jnp.int8),
                         jnp.argmax(new).astype(jnp.int32))

        sigma, (sel, amax) = jax.lax.scan(step_renorm, sigma0,
                                          (bm_half, norm_mask))
        return sel, sigma, amax

    def step(sigma, bmh):
        bm = expand_half(bmh, trellis)                # (2^beta,)
        cand0 = sigma[prev_state[:, 0]] + bm[prev_out[:, 0]]
        cand1 = sigma[prev_state[:, 1]] + bm[prev_out[:, 1]]
        sel = (cand1 >= cand0)                        # Alg.1: ties -> i''
        new = jnp.where(sel, cand1, cand0)
        new = new - jnp.max(new)                      # normalize (DESIGN §8)
        return new, (sel.astype(jnp.int8), jnp.argmax(new).astype(jnp.int32))

    if radix == 4:
        n = bm_half.shape[0]
        n2 = n // 2

        def pair(sigma, bmh2):                        # bmh2: (2, half)
            sigma, (sel_a, am_a) = step(sigma, bmh2[0])
            sigma, (sel_b, am_b) = step(sigma, bmh2[1])
            return sigma, (jnp.stack([sel_a, sel_b]),
                           jnp.stack([am_a, am_b]))

        sigma, (sel, amax) = jax.lax.scan(
            pair, sigma0, bm_half[:2 * n2].reshape(n2, 2, -1))
        sel, amax = sel.reshape(2 * n2, S), amax.reshape(2 * n2)
        if n % 2:                                     # odd-length tail stage
            sigma, (sel_t, am_t) = step(sigma, bm_half[-1])
            sel = jnp.concatenate([sel, sel_t[None]])
            amax = jnp.concatenate([amax, am_t[None]])
        return sel, sigma, amax

    sigma, (sel, amax) = jax.lax.scan(step, sigma0, bm_half)
    return sel, sigma, amax


def viterbi_traceback(sel: jax.Array, trellis: Trellis, start_state: jax.Array,
                      num_steps: int | None = None):
    """Alg. 2: serial traceback from ``start_state`` over all of ``sel``.

    Returns (bits, states): bits[t] is the decoded input bit of stage t;
    states[t] is the survivor state AT stage t (after consuming bit t).
    """
    prev_state = jnp.asarray(trellis.prev_state)
    kshift = trellis.k - 2

    def step(j, sel_t):
        bit = j >> kshift                             # alpha_in into state j
        p = sel_t[j].astype(jnp.int32)
        i = prev_state[j, p]
        return i, (bit, j)

    _, (bits, states) = jax.lax.scan(
        step, start_state.astype(jnp.int32), sel.astype(jnp.int32),
        reverse=True)
    return bits.astype(jnp.int32), states


@partial(jax.jit, static_argnums=(1, 2))
def viterbi_decode(llr: jax.Array, trellis: Trellis,
                   radix: int = 2) -> jax.Array:
    """Full-sequence decode: (n, beta) llr -> (n,) bits. Table I row (a)."""
    S = trellis.num_states
    # the encoder starts in state 0: bias the initial metrics
    sigma0 = jnp.full((S,), NEG).at[0].set(0.0)
    sel, sigma, _ = viterbi_forward(llr, trellis, sigma0, radix)
    start = jnp.argmax(sigma).astype(jnp.int32)
    bits, _ = viterbi_traceback(sel, trellis, start)
    return bits
