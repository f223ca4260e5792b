"""Jitted public wrapper around the unified Pallas Viterbi kernel.

Handles frame-count padding to the tile size, resolves the
``frames_per_tile='auto'`` tile plan (kernels/autotune.py), and exposes
one call the rest of the framework uses: ``viterbi_decode_frames``.

Defaults are the library's best-known configuration (bit-packed survivors,
radix-4, autotuned tiles — the same defaults as core.pipeline.DecoderConfig);
pass ``pack_survivors=False, radix=2, frames_per_tile=8`` explicitly to
reproduce the seed kernel behavior.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.framed import FrameSpec, merge_blocks, reframe_blocks
from ..core.pipeline import platform_defaults
from ..core.trellis import Trellis
from ..obs.tracer import get_tracer
from .autotune import plan_tiles
from .viterbi_unified import unified_decode_frames

__all__ = ["viterbi_decode_frames"]


def _pad_frames(frames: jax.Array, tile: int):
    F = frames.shape[0]
    Fp = -(-F // tile) * tile
    if Fp != F:
        frames = jnp.pad(frames, ((0, Fp - F), (0, 0), (0, 0)))
    return frames, F


@partial(jax.jit, static_argnames=("trellis", "spec", "frames_per_tile",
                                   "pack_survivors", "radix",
                                   "block_frames", "overlap", "interpret"))
def viterbi_decode_frames(frames: jax.Array, trellis: Trellis,
                          spec: FrameSpec, *,
                          frames_per_tile: int | str = "auto",
                          pack_survivors: bool = True, radix: int = 4,
                          block_frames: int = 1, overlap: int = 0,
                          interpret: bool | None = None) -> jax.Array:
    """(F, L, beta) LLR frames -> (F, f) decoded bits, with the paper's
    single-kernel path (survivors in VMEM only).

    frames_per_tile: frames decoded per kernel grid step, or 'auto' to let
                    the VMEM-budget planner choose (autotune.plan_tiles).
    pack_survivors: bit-pack the VMEM survivor scratch 32x.
    radix         : 2, or 4 to fuse two trellis stages per ACS/traceback
                    step. Every combination of these three decodes
                    bit-identically.
    block_frames  : >1 engages intra-frame block-parallel decode
                    (kernels/block.py): each frame re-framed into
                    block_frames blocks of f/B + 2*overlap stages on the
                    frame axis, decoded by this same kernel under the
                    derived spec, merged by truncating each block's
                    overlap. The one knob that is not bit-exact: a
                    truncated-traceback approximation, BER-gated to 1e-3
                    at overlap ~5*K, and exactly bit-identical when
                    overlap >= block.full_overlap().
    overlap       : per-block training/truncation region (stages); only
                    meaningful with block_frames > 1.
    interpret     : run the Pallas kernel in the interpreter. None takes
                    the platform's: compiled on a TPU, interpreted
                    elsewhere. A compiled request for a tile Mosaic cannot
                    run raises ValueError instead of falling back to the
                    interpreter.
    """
    if interpret is None:
        interpret = platform_defaults()["interpret"]
    spec.validate()
    # entry validation (trace-time, so invalid calls fail with a clear
    # message instead of a shape error deep inside a kernel)
    if frames.ndim != 3:
        raise ValueError(
            f"frames must be (F, L, beta), got {frames.ndim}-D "
            f"{frames.shape}")
    if frames.shape[1] != spec.frame_len:
        raise ValueError(
            f"frames.shape[1]={frames.shape[1]} != spec.frame_len="
            f"{spec.frame_len} (v1 + f + v2 overlap window)")
    if frames.shape[2] != trellis.beta:
        raise ValueError(
            f"frames.shape[2]={frames.shape[2]} != trellis.beta="
            f"{trellis.beta} coded bits per stage")
    if not jnp.issubdtype(frames.dtype, jnp.floating):
        raise ValueError(
            f"frames must be floating-point LLRs, got dtype "
            f"{frames.dtype}")
    F_in = frames.shape[0]
    if block_frames < 1:
        raise ValueError(f"block_frames must be >= 1, got {block_frames}")
    if block_frames > 1:
        # intra-frame block-parallel mode: re-frame (F, L) frames into
        # (F*B, f/B + 2*overlap) blocks on the same frame axis and decode
        # them below under the derived spec — the tile planner, padding,
        # kernels and traceback all see ordinary (short) frames
        sub = spec.blocked(block_frames, overlap)
        frames = reframe_blocks(frames, spec, block_frames, overlap)
        spec = sub
    if frames_per_tile == "auto":
        frames_per_tile = plan_tiles(
            trellis, spec, pack_survivors=pack_survivors,
            max_frames=frames.shape[0]).frames_per_tile
    # serial traceback == one subframe spanning the kept region (DESIGN §2)
    f0 = spec.f0 if spec.parallel_tb else spec.f
    v2s = spec.v2s if spec.parallel_tb else spec.v2
    start = spec.start if spec.parallel_tb else "boundary"

    # This function body runs at jit *trace* time only — so this event
    # marks each real XLA compile of a decode program (re-launches of the
    # cached executable never reach here). One glance at a trace file
    # answers "how many distinct kernels did this run compile, and with
    # which knobs?".
    trace = get_tracer()
    trace.event("kernel_trace", states=trellis.num_states,
                frames=int(frames.shape[0]),
                frames_per_tile=int(frames_per_tile), radix=int(radix),
                pack_survivors=bool(pack_survivors),
                block_frames=int(block_frames), overlap=int(overlap),
                interpret=bool(interpret))
    trace.count("kernel_traces")

    padded, F = _pad_frames(frames, frames_per_tile)
    bits = unified_decode_frames(
        padded, trellis=trellis, v1=spec.v1, f=spec.f, v2=spec.v2,
        f0=f0, v2s=v2s, start=start, frames_per_tile=frames_per_tile,
        pack_survivors=pack_survivors, radix=radix,
        interpret=interpret)[:F]
    if block_frames > 1:
        bits = merge_blocks(bits, block_frames)       # (F_in, f)
        assert bits.shape[0] == F_in
    return bits
