"""Traceback strategies (paper §IV-D).

Two tracebacks over one frame's survivor selectors ``sel`` (L, S):

* ``serial_traceback``   — one cursor chases the whole frame (prior work).
* ``parallel_traceback`` — the frame's kept region is split into ``nsub``
  subframes of ``f0`` stages; every subframe is traced back concurrently,
  each with a right-overlap of ``v2s`` stages for survivor-path convergence
  (paper Fig. 5). Start states are either the per-stage argmax states
  recorded in the forward pass (``start='boundary'``, the paper's preferred
  solution) or a fixed state (``start='fixed'``, reproduces Fig. 11's
  degradation).

The parallel version is a *vectorized pointer chase*: all ``nsub`` cursors
advance together, so the backward pass costs f0+v2s vector steps instead of
f+v2 serial steps — the D/D' parallelism of Table I row (c).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .trellis import Trellis

__all__ = ["serial_traceback", "parallel_traceback"]


def serial_traceback(sel: jax.Array, trellis: Trellis, start_state: jax.Array,
                     v1: int, f: int) -> jax.Array:
    """Chase from the last stage; return the f kept bits [v1, v1+f)."""
    prev_state = jnp.asarray(trellis.prev_state)
    kshift = trellis.k - 2

    def step(j, sel_t):
        bit = j >> kshift
        i = prev_state[j, sel_t[j]]
        return i, bit

    _, bits = jax.lax.scan(step, start_state.astype(jnp.int32),
                           sel.astype(jnp.int32), reverse=True)
    return jax.lax.dynamic_slice(bits, (v1,), (f,))


def parallel_traceback(sel: jax.Array, amax: jax.Array, trellis: Trellis,
                       v1: int, f: int, f0: int, v2s: int,
                       start: str = "boundary") -> jax.Array:
    """Parallel traceback over ``nsub = f // f0`` subframes.

    Args:
      sel:  (L, S) selector bits from the forward pass, L >= v1 + f + v2s.
      amax: (L,) per-stage argmax states (used when start == 'boundary').
      v1/f: kept region is stages [v1, v1+f).
      f0:   subframe length (f % f0 == 0).
      v2s:  subframe right-overlap (convergence) length; the frame's own
            right overlap v2 must be >= v2s so the last subframe's chase
            start stays inside the frame.
      start: 'boundary' | 'fixed'.

    Returns: (f,) decoded bits.
    """
    assert f % f0 == 0, "f must be a multiple of f0 (paper §IV-E alignment)"
    nsub = f // f0
    L = sel.shape[0]
    assert v1 + f + v2s <= L, "need v2 >= v2s"
    prev_state = jnp.asarray(trellis.prev_state)
    kshift = trellis.k - 2

    q = jnp.arange(nsub, dtype=jnp.int32)
    # chase start stage of subframe q (inclusive): end of kept region + v2s
    e = v1 + (q + 1) * f0 - 1 + v2s                   # (nsub,)
    if start == "boundary":
        states = amax[e].astype(jnp.int32)
    elif start == "fixed":
        states = jnp.zeros((nsub,), jnp.int32)
    else:
        raise ValueError(start)

    sel32 = sel.astype(jnp.int32)

    def step(states, r):
        t = e - r                                     # (nsub,) current stages
        bits = states >> kshift
        p = sel32[t, states]                          # vectorized gather
        states = prev_state[states, p]
        return states, bits

    # chase f0 + v2s steps; the first v2s emitted bits per subframe are the
    # convergence overlap and are discarded (paper: "not stored")
    _, bits = jax.lax.scan(step, states, jnp.arange(f0 + v2s, dtype=jnp.int32))
    kept = bits[v2s:, :]                              # (f0, nsub), r-ordered
    # r = v2s + m corresponds to stage e - v2s - m = v1 + (q+1)*f0 - 1 - m:
    # reverse the step axis to get stage-ascending order within the subframe
    kept = kept[::-1, :]                              # (f0, nsub) ascending
    return kept.T.reshape((f,))                       # subframes concatenated

