"""Unified Viterbi kernel (paper §IV-A, Alg. 3) as a Pallas TPU kernel.

The paper's central idea: fuse the forward procedure (branch metrics + ACS +
survivor paths) and the backward procedure (parallel traceback + decode) into
ONE kernel so the survivor-path matrix lives in on-chip memory (GPU shared
memory -> TPU **VMEM scratch**) and never touches HBM. The only HBM traffic
is the LLR block in and the decoded bits out — Table I row (c): global memory
for intermediate data = none.

TPU mapping (DESIGN.md §2):
  * grid = frame tiles; each grid step decodes ``FT`` frames entirely in VMEM
    (FT plays the role of "multiple frames per block" from §IV-F).
  * the ACS butterfly is arithmetic, not gathers: prev(j,p) = ((j<<1)&(S-1))|p,
    so the traceback pointer chase is pure vector integer ops.
  * branch metrics are precomputed coalesced (paper Fig. 7) in the
    symmetry-compressed 2^(beta-1) form (eq. 9) into float32 VMEM scratch.
  * the parallel traceback advances all ``nsub`` subframe cursors of all
    ``FT`` frames in lock-step: the backward pass costs f0+v2s vector steps.

Memory layout (paper §IV-F "multiple frames per block" meets the TPU's
(8 sublane x 128 lane) tiles): frames fill the 128 lanes and every array
is 2-D with a stage-major flat sublane axis, so the tiny W/half dims are
absorbed into the sublane axis instead of being padded to a full tile. A
tile of FT frames is C = FT/128 lane chunks of 128 frames (one chunk of FT
when FT < 128 covers the whole batch), since Mosaic's strided loads and
stores need a 128-lane base array; every ref is (C, rows, lanes) and each
stage runs the chunks side by side. The wrapper hands the kernel the LLRs
as (F/lanes, L*beta, lanes) and takes the bits back as (F/lanes, f,
lanes); the transposes run in XLA, outside the kernel. Per grid step, per
chunk (``autotune.kernel_shapes``, which this module allocates from):
  llr block    (L*beta, lanes) f32    bm (eq. 9)  (half*L, lanes) f32
  survivors    (L*W, lanes) i32 packed, (L*S, lanes) unpacked
  argmax       (L, lanes) i32         path metrics (S, lanes) f32
  out block    (f, lanes) i32
The traceback reads the ``nsub`` subframes' survivor words with one
strided load per word and writes their bits straight into the output
block with one strided store, stage-ascending — no gather, no reversal
and no in-kernel transpose.

The same kernel runs compiled by Mosaic on a TPU and in the Pallas
interpreter elsewhere; a compiled call refuses a frame tile Mosaic cannot
tile rather than interpreting it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.trellis import Trellis
from .acs import acs_scan, stage_loop
from .autotune import LANES, kernel_shapes, vmem_limit
from .packing import pack_rows, packed_width

__all__ = ["unified_decode_frames", "check_compiled"]


def check_compiled(frames_per_tile: int, frames: int) -> None:
    """Refuse, with the reason, a compiled launch Mosaic cannot run."""
    if frames_per_tile % LANES and frames_per_tile != frames:
        raise ValueError(
            f"frames_per_tile={frames_per_tile} is neither a multiple of "
            f"{LANES} lanes nor the whole padded batch ({frames} frames); "
            f"Mosaic cannot tile it")


def _kernel_sublane(llr_ref, out_ref, sel_ref, amax_ref, bm_ref, sigma_ref,
                    *, trellis: Trellis, v1: int, f: int, v2: int, f0: int,
                    v2s: int, start: str, pack: bool, radix: int):
    # (the name is the Pallas call's kernel_name in the compiled program
    # and its traces, so it stays as the chip has always run it)
    S = trellis.num_states
    S2 = S // 2
    kshift = trellis.k - 2
    L = v1 + f + v2
    C, _, lanes = out_ref.shape                      # lane chunks of the tile
    nsub = f // f0
    R = packed_width(S) if pack else S               # survivor rows / stage
    state_ids = jax.lax.broadcasted_iota(jnp.int32, (S, lanes), 0)

    # ---- phases 1+2: branch metrics + ACS, survivors stay in VMEM --------
    def store(t, c, sel):
        if pack:
            words = pack_rows(jnp.concatenate(
                [s.astype(jnp.int32) for s in sel], axis=0))
            for w, word in enumerate(words):
                sel_ref[c, pl.ds(t * R + w, 1), :] = word
        else:
            for hh, s in enumerate(sel):
                sel_ref[c, pl.ds(t * R + hh * S2, S2), :] = \
                    s.astype(jnp.int32)
        # argmax of the normalized metrics = first state at exactly 0
        # (Mosaic's argmax does not return the first of tied maxima)
        amax_ref[c, pl.ds(t, 1), :] = jnp.min(
            jnp.where(sigma_ref[c] == 0.0, state_ids, S), axis=0,
            keepdims=True)

    acs_scan(llr_ref, bm_ref, sigma_ref, trellis=trellis, L=L, radix=radix,
             store=store)

    # ---- phase 3: parallel traceback (paper §IV-D, Fig. 5) ---------------
    # subframe q starts its chase at stage e0 + q*f0; one strided load
    # reads row `row` of all nsub subframes' stages at once
    e0 = v1 + f0 - 1 + v2s

    def strided(ref, c, row, step):
        if nsub == 1:
            return ref[c, pl.ds(row, 1), :]
        return ref[c, pl.ds(row, nsub, stride=step), :]

    if start == "boundary":
        states = tuple(strided(amax_ref, c, e0, f0) for c in range(C))
    else:                                            # 'fixed' (Fig. 11)
        states = (jnp.zeros((nsub, lanes), jnp.int32),) * C

    def chase_chunk(r, c, states):                   # stage e0 + q*f0 - r
        row = (e0 - r) * R
        word_id = states >> 5 if pack else states
        word = strided(sel_ref, c, row, f0 * R)
        for w in range(1, R):
            word = jnp.where(word_id == w,
                             strided(sel_ref, c, row + w, f0 * R), word)
        p = (word >> (states & 31)) & 1 if pack else word
        return ((states << 1) & (S - 1)) | p         # butterfly arithmetic

    def chase(r, states):
        return tuple(chase_chunk(r, c, s) for c, s in enumerate(states))

    def keep(r, states):                             # kept region, direct
        m = f0 - 1 + v2s - r                         # stage v1 + q*f0 + m
        for c, s in enumerate(states):
            if nsub == 1:
                out_ref[c, pl.ds(m, 1), :] = s >> kshift
            else:
                out_ref[c, pl.ds(m, nsub, stride=f0), :] = s >> kshift
        return chase(r, states)

    # the first v2s steps only converge (their bits are not stored)
    states = stage_loop(0, v2s, chase, states, radix)
    stage_loop(v2s, v2s + f0, keep, states, radix)


@functools.partial(jax.jit, static_argnames=(
    "trellis", "v1", "f", "v2", "f0", "v2s", "start", "frames_per_tile",
    "pack_survivors", "radix", "interpret"))
def unified_decode_frames(frames: jax.Array, *, trellis: Trellis, v1: int,
                          f: int, v2: int, f0: int, v2s: int,
                          start: str = "boundary", frames_per_tile: int = 8,
                          pack_survivors: bool = False, radix: int = 2,
                          interpret: bool) -> jax.Array:
    """Decode (F, L, beta) LLR frames -> (F, f) bits with the unified kernel.

    F must be a multiple of ``frames_per_tile`` (ops.py pads).
    ``pack_survivors`` bit-packs the VMEM survivor scratch 32x; ``radix=4``
    fuses two trellis stages per ACS/traceback step. Both are bit-exact.
    """
    F, L, beta = frames.shape
    assert L == v1 + f + v2, (L, v1, f, v2)
    assert f % f0 == 0 and v2s <= v2
    assert radix in (2, 4), radix
    FT = frames_per_tile
    assert F % FT == 0, (F, FT)
    if not interpret:
        check_compiled(FT, F)
    kw = dict(trellis=trellis, v1=v1, f=f, v2=v2, f0=f0, v2s=v2s,
              start=start, pack=pack_survivors, radix=radix)

    # LLRs in as (F/lanes, L*beta, lanes), bits out as (F/lanes, f,
    # lanes) — XLA does the transposes, outside the kernel
    shapes = kernel_shapes(trellis, L, f, FT, pack_survivors)
    (_, llr_block, _), (_, out_block, _) = shapes[:2]
    lanes = llr_block[-1]
    llr = (frames.astype(jnp.float32).reshape(F // lanes, lanes, L * beta)
           .transpose(0, 2, 1))
    bits = pl.pallas_call(
        functools.partial(_kernel_sublane, **kw),
        grid=(F // FT,),
        in_specs=[pl.BlockSpec(llr_block, lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec(out_block, lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F // lanes, f, lanes), jnp.int32),
        scratch_shapes=[pltpu.VMEM(s, d) for _, s, d in shapes[2:]],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(shapes)),
        interpret=interpret,
    )(llr)
    return bits.transpose(0, 2, 1).reshape(F, f)
