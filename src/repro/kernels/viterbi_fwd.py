"""Forward-only Viterbi kernel — the prior-work baseline (Table I row b).

Same ACS as the unified kernel, but the survivor selectors are STREAMED TO
HBM (the GPU papers' "global memory") and traced back by a separate step.
Exists so the unified kernel's memory-traffic win is measurable:
  survivor-path HBM traffic here = F * L * S * 1 byte  (written then re-read)
  survivor-path HBM traffic in the unified kernel = 0.

``pack_survivors`` bit-packs the streamed selectors into int32 words
(kernels/packing.py): F * L * ceil(S/32) * 4 bytes on the wire — 8x less
than the int8 stream — which keeps the split-vs-unified comparison honest
once the unified kernel packs its VMEM scratch. ``radix=4`` fuses two
trellis stages per scan step (see tables.radix4_tables); both knobs are
bit-exact vs the radix-2 / unpacked seed kernel.

The stream is frame-major (the lane layout, kernels/packing.Layout):
(F, L, W) int32 packed words or (F, L, S) int8 selectors. ``bm_dtype``
sets the branch-metric scratch dtype (see acs.py).

This baseline runs in interpret mode and in the lane layout only; on the
chip the unified kernel is the decode path, and a compiled or sublane
request here raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.trellis import Trellis
from .acs import acs_scan_lane
from .packing import Layout, pack_bits, packed_width

__all__ = ["forward_frames"]


def _kernel(llr_ref, sel_ref, amax_ref, bm_ref, *, trellis: Trellis, L: int,
            pack: bool, radix: int, bm_dtype):
    # same forward recursion as the unified kernel (shared via acs.py);
    # only the survivor destination differs: HBM-backed output refs.
    def store(t, sel, sigma):                        # sel/sigma are (FT, S)
        if pack:
            sel_ref[:, t, :] = pack_bits(sel)        # -> HBM, 1 bit/state
        else:
            sel_ref[:, t, :] = sel.astype(jnp.int8)  # 1 byte/state
        amax_ref[:, t] = jnp.argmax(sigma, axis=1).astype(jnp.int32)

    acs_scan_lane(llr_ref, bm_ref, trellis=trellis, L=L, radix=radix,
                  store=store, bm_dtype=bm_dtype)


@functools.partial(jax.jit, static_argnames=(
    "trellis", "frames_per_tile", "pack_survivors", "radix", "layout",
    "bm_dtype", "interpret"))
def forward_frames(frames: jax.Array, *, trellis: Trellis,
                   frames_per_tile: int = 8, pack_survivors: bool = False,
                   radix: int = 2, layout: str = "lane",
                   bm_dtype: str = "float32", interpret: bool = True):
    """(F, L, beta) llr -> (sel, amax (F, L) int32) in HBM.

    sel: (F, L, S) int8, or packed (F, L, ceil(S/32)) int32. Interpret mode
    and the lane layout only: any other request raises ``ValueError``.
    """
    if not interpret:
        raise ValueError(
            "the split kernel (backend='kernel_split') runs only in "
            "interpret mode: its per-stage column stores into the HBM "
            "argmax stream do not lower on Mosaic; use the unified kernel "
            "(backend='kernel') with interpret=False")
    if Layout(layout) is not Layout.LANE:
        raise ValueError(
            "the split kernel (backend='kernel_split') runs only in the "
            "lane layout; use layout='lane', or the unified kernel for "
            "layout='sublane'")
    F, L, beta = frames.shape
    FT = frames_per_tile
    assert F % FT == 0, (F, FT)
    assert radix in (2, 4), radix
    bm_dt = jnp.dtype(bm_dtype)
    S = trellis.num_states
    half = 1 << (trellis.beta - 1)
    sel_w = packed_width(S) if pack_survivors else S
    sel_dt = jnp.int32 if pack_survivors else jnp.int8
    kern = functools.partial(_kernel, trellis=trellis, L=L,
                             pack=pack_survivors, radix=radix, bm_dtype=bm_dt)
    return pl.pallas_call(
        kern,
        grid=(F // FT,),
        in_specs=[pl.BlockSpec((FT, L, beta), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((FT, L, sel_w), lambda i: (i, 0, 0)),
                   pl.BlockSpec((FT, L), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((F, L, sel_w), sel_dt),
                   jax.ShapeDtypeStruct((F, L), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((L, FT, half), bm_dt)],
        interpret=interpret,
    )(frames)
