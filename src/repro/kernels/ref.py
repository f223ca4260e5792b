"""Pure-jnp oracle for the Pallas kernel.

Deliberately written on top of the already-unit-tested ``repro.core``
reference algorithms (which are themselves validated against the encoder
round-trip and a hand-written numpy encoder), so kernel == ref == Alg. 1+2.
"""
from __future__ import annotations

from functools import partial

import jax

from ..core.framed import FrameSpec, decode_frame
from ..core.trellis import Trellis

__all__ = ["unified_decode_frames_ref"]


@partial(jax.jit, static_argnums=(1, 2))
def unified_decode_frames_ref(frames: jax.Array, trellis: Trellis,
                              spec: FrameSpec) -> jax.Array:
    """(F, L, beta) -> (F, f) bits; oracle for viterbi_unified."""
    return jax.vmap(lambda fr: decode_frame(fr, trellis, spec))(frames)

